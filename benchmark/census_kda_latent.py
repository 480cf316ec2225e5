#!/usr/bin/env python3
"""Which shapes does a traffic mix reach on the ``kda_latent_moe`` family's
paged path? The family's twin of ``census_latent_moe.py`` (a CPU tool, for
whoever writes a mix's ``warm`` section): it runs the mix's schedule through
the engine on the tiny ``KdaLatentConfig`` with the mix's own ``engine``
section and counts the (batch, pages, pool rows) buckets of the fused step,
the context lengths the page program is handed (before and after the
family pads them) and how often a carry changed seats. Shapes follow token
counts and capacities, not widths. It counts; it measures nothing.

    JAX_PLATFORMS=cpu python3 benchmark/census_kda_latent.py --traffic state-decode --seeds 1 --requests 150
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def census(traffic: str, seeds: list[int], requests: int) -> dict:
    import jax

    import harness
    from oncilla_tpu import models as program_models

    # The family's model module, where its adapters look the programs up.
    kda_latent = sys.modules[program_models.KdaLatentConfig.__module__]
    with open(os.path.join(HERE, "traffic", f"{traffic}.json")) as f:
        spec = json.load(f)
    cfg = kda_latent.KdaLatentConfig.tiny()
    P = int(spec["engine"]["page_tokens"])
    buckets: collections.Counter = collections.Counter()
    contexts: collections.Counter = collections.Counter()
    padded: collections.Counter = collections.Counter()
    fused, prefill = (kda_latent.kda_decode_batch_step_jit,
                      kda_latent.kda_decode_page_jit)

    def count_fused(params, toks, metas, n_real, pool, table, *rest):
        buckets[(toks.shape[0], table.shape[1], pool.shape[0])] += 1
        return fused(params, toks, metas, n_real, pool, table, *rest)

    def count_prefill(params, toks, meta, ctx, *rest):
        contexts[int(meta[0]) // P] += 1
        padded[ctx.shape[3] // P] += 1
        return prefill(params, toks, meta, ctx, *rest)

    kda_latent.kda_decode_batch_step_jit = count_fused
    kda_latent.kda_decode_page_jit = count_prefill
    by_seed: dict = {}
    try:
        gen = harness.load_plugin("generators", spec["generator"])
        for seed in seeds:
            params = kda_latent.init_params(jax.random.key(seed), cfg)
            problems: list = []
            with harness.serving_stack(cfg, params, spec["engine"], "census",
                                       problems) as (engine, _):
                loop = harness.Loop(
                    engine, gen.schedule(seed, spec["params"], cfg.vocab))
                loop.run_until(lambda: len(loop.done) >= requests)
                meta = engine.metrics_meta()
                by_seed[seed] = {
                    "ticks": loop.ticks, "hops": meta["moves"]["hops"],
                    "tier_pages_peak": meta["tier_pages_peak"],
                    "stalls": meta["stalls"], "moe": meta["moe"],
                    "carry": meta["carry"],
                    "batch_steps": meta["batch"]["steps"]}
                loop.drain()
            if problems:
                raise RuntimeError(f"seed {seed}: {problems}")
    finally:
        kda_latent.kda_decode_batch_step_jit = fused
        kda_latent.kda_decode_page_jit = prefill
    return {"fused_buckets": sorted([list(k), n] for k, n in buckets.items()),
            "prefill_context_pages": sorted(contexts.items()),
            "prefill_padded_pages": sorted(padded.items()),
            "by_seed": by_seed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--requests", type=int, default=150)
    args = ap.parse_args(argv)
    out = census(args.traffic, [int(s) for s in args.seeds.split(",")],
                 args.requests)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
