"""Requests whose prompt and output lengths are lognormal, optionally behind
one shared system prompt, arriving from a closed loop of clients, a Poisson
process or Poisson bursts. The one general generator of this benchmark: a
traffic mix is a file of its parameters (``benchmark/traffic/*.json``).

Every seed gets the SAME (prompt length, output length) pairs, the ``pool``,
in the SAME order, with other token ids (and the harness makes other weights):
the lengths are the stratified quantiles of the two lognormals (no sampling
noise), paired and ordered by permutations fixed by ``shape_seed``. So two
seeds offer the same work, and a run-to-run difference is the system's, not
the draw's: with the order drawn from the seed, three seeds differed by 7 % in
tokens/s and 17 % in the TTFT tail while two runs of one seed agreed within
2 % (PERF.md section 6, PR 24).

``schedule(seed, params, vocab)`` returns ``{"clients", "nth"}``:
``nth(i)`` is the i-th request of the run, ``{"tokens", "max_new_tokens"}``
and, for an open loop, ``"at_s"``, the second after the loop's start at
which it is due. ``clients`` is the closed loop's population (0 = open).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantile_lengths(spec: dict, n: int) -> list[int]:
    """n stratified quantiles of lognormal(median, sigma), clipped."""
    mu = math.log(spec["median"])
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def pool(params: dict) -> list[tuple[int, int]]:
    """The (unshared prompt tokens, new tokens) pairs every seed offers."""
    n = int(params["pool"])
    prompts = _quantile_lengths(params["prompt"], n)
    outs = _quantile_lengths(params["new_tokens"], n)
    shared = int(params.get("shared_prefix_tokens", 0))
    avoid = int(params.get("avoid_multiple_of", 0))
    if avoid:
        # A prompt of whole pages gets its first token from the prefill
        # program itself and never waits for a seat: real prompts do not
        # land on a page boundary, so neither do these.
        lo, hi = params["prompt"]["min"], params["prompt"]["max"]
        prompts = [
            p if (shared + p) % avoid else (p + 1 if p < hi else max(p - 1, lo))
            for p in prompts
        ]
    rng = np.random.default_rng(int(params.get("shape_seed", 0)))
    outs = [outs[j] for j in rng.permutation(n)]
    return list(zip(prompts, outs))


def arrivals(seed: int, spec: dict, n: int) -> list[float] | None:
    """Due times of the first n requests, or None for a closed loop."""
    kind = spec.get("kind", "closed")
    if kind == "closed":
        return None
    rng = np.random.default_rng([int(seed), 0xA221])
    if kind == "poisson":
        return np.cumsum(rng.exponential(1.0 / spec["rate_per_s"], n)).tolist()
    if kind == "bursts":
        # Bursts arrive as a Poisson process; each brings `burst` requests
        # at once. The mean request rate is rate_per_s.
        b = int(spec["burst"])
        starts = np.cumsum(rng.exponential(b / spec["rate_per_s"], -(-n // b)))
        return np.repeat(starts, b)[:n].tolist()
    raise ValueError(f"unknown arrivals kind {kind!r}")


def schedule(seed: int, params: dict, vocab: int) -> dict:
    sizes = pool(params)
    n = len(sizes)
    order = np.random.default_rng(
        [int(params.get("shape_seed", 0)), 0x51E5]).permutation(n)
    shared_n = int(params.get("shared_prefix_tokens", 0))
    shared = np.random.default_rng([int(seed), 0x5A4D]).integers(
        1, vocab, shared_n).tolist()
    arr_spec = params.get("arrivals", {"kind": "closed"})
    horizon = int(params.get("horizon_requests", 4096))
    due = arrivals(seed, arr_spec, horizon)

    def nth(i: int) -> dict:
        p, new = sizes[int(order[i % n])]
        own = np.random.default_rng([int(seed), 0x70C5, i]).integers(
            1, vocab, p).tolist()
        req = {"tokens": shared + own, "max_new_tokens": int(new)}
        if due is not None:
            if i >= horizon:
                raise IndexError(f"request {i} past horizon_requests={horizon}")
            req["at_s"] = float(due[i])
        return req

    closed = due is None
    return {"clients": int(params["clients"]) if closed else 0, "nth": nth}
