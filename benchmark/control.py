#!/usr/bin/env python3
"""The control of ``correct``: does the comparison with the plain reference
fail a model computed one precision lower than its configuration states?

For each seed it makes the configuration's weights as a run does (the
family's ``init_params`` from the seed's key), draws one sequence of token
ids as long as the mixes' longest request, and puts in the program's place
the family's reference over the same weights rounded to the nearest
precision below ``torch_dtype`` (float8_e4m3's three mantissa bits under
bfloat16 and float16, bfloat16's seven under float32): the step that would
tempt a later PR. The exponent's range is kept, as an ideally scaled fp8
tensor keeps it: the mildest form of the lower precision, so the hardest
control to catch. Rounding is ``jax.lax.reduce_precision``: a jitted pair
of casts (down and up again) was measured to round nothing on the TPU, and
the first control built from one read exactly 0 (PERF.md section 6, PR 28).
Its logits
are held to the configuration's ``tolerance`` against the reference over
the unrounded weights, by the numbers a run compares (the widest logit gap,
the share of equal arg-maxes, and how far the token the lower precision
puts first lies below the reference's best). A tolerance that
lets the control through is too wide. The benchmark's own runs do not run
this; a limit is set with it (``README.md``, PERF.md section 2).

    python3 benchmark/control.py --config mistral-7b-v0.1-d16 --seeds 1,2,3

Exit 0 when every seed's control came out NOT correct, 1 otherwise. It runs
on whatever backend JAX has: a reading for a limit comes from the chip, at
the configuration's own size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

# The nearest precision below each type a configuration may state: its
# name, and the (exponent, mantissa) bits the weights are rounded to.
LOWER = {"float32": ("bfloat16", 8, 7), "bfloat16": ("float8_e4m3", 8, 3),
         "float16": ("float8_e4m3", 5, 3)}


def control(conf: dict, seed: int, tokens: int) -> dict:
    """The numbers of ``harness.check_reference`` and ``check_served`` with
    the lower precision in the program's place, beside their limits."""
    import jax
    import jax.numpy as jnp

    import harness

    family = harness.load_family(conf)
    _, params = harness.seeded_weights(family, conf, seed)
    rng = np.random.default_rng(seed)
    seq = rng.integers(1, int(conf["vocab_size"]), (1, tokens)).astype(np.int32)
    rows = np.arange(tokens)
    ref = family.reference.logits_at(params, seq, rows, conf)[0]
    served = jnp.dtype(conf["torch_dtype"])
    lower, exponent_bits, mantissa_bits = LOWER[conf["torch_dtype"]]
    # In place (the weights donated): two copies of them do not fit beside
    # the reference's float32 layer. Leaves of another type than the served
    # one (float32 norm gains under bf16 weights) stay as they are.
    rounded = jax.jit(
        lambda p: jax.tree.map(
            lambda w: jax.lax.reduce_precision(w, exponent_bits, mantissa_bits)
            if w.dtype == served else w, p),
        donate_argnums=0)(params)
    low = family.reference.logits_at(rounded, seq, rows, conf)[0]
    tol = conf["tolerance"]
    dmax = float(np.abs(ref - low).max())
    share = float((ref.argmax(-1) == low.argmax(-1)).mean())
    # What the lower precision would have served: the token it puts first.
    gap = float((ref.max(-1) - ref[rows, low.argmax(-1)]).max())
    gap_limit = conf["tolerance_served"]["max_logit_gap"]
    return {"seed": seed, "lower": lower, "tokens_compared": tokens,
            "max_abs_dlogit": {"value": dmax, "limit": tol["max_abs_dlogit"]},
            "argmax_share": {"value": share, "limit": tol["argmax_share"]},
            "served_logit_gap": {"value": gap, "limit": gap_limit},
            "correct": bool(dmax <= tol["max_abs_dlogit"]
                            and share >= tol["argmax_share"]
                            and gap <= gap_limit)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="configs/<name>.json")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--tokens", type=int, default=346,
                    help="positions compared (the longest request of the "
                         "committed mixes: 250 + 96)")
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        conf = json.load(f)
    passed_through = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control(conf, seed, args.tokens)
        print(json.dumps(out), flush=True)
        passed_through += out["correct"]
    return 1 if passed_through else 0


if __name__ == "__main__":
    sys.exit(main())
