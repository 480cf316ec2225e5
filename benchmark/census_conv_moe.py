#!/usr/bin/env python3
"""Which shapes does a traffic mix reach on the ``conv_gqa_moe`` family's
paged path, and how many slots of HOT does its prefix cache fill? The
family's twin of ``census_kda_latent.py`` (a CPU tool, for whoever writes a
mix's ``warm`` and ``engine`` sections): it runs the mix's schedule through
the engine on the tiny ``ConvMoeConfig`` with the mix's own ``engine``
section, warm-up ramp first as the harness runs it, and counts the (batch,
pages, pool rows) buckets of the fused step, the context lengths the page
program is handed (before and after the family pads them), and, at
``--marks`` completed requests, the pages each tier holds, the prefix
extents and the carry snapshots: nothing reclaims a dead extent, so HOT has
to hold every page and snapshot a run publishes. Shapes and slots follow
token counts and capacities, not widths. It counts; it measures nothing.

    JAX_PLATFORMS=cpu python3 benchmark/census_conv_moe.py --traffic agent-prefix --requests 700 --marks 65,400,700
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


def census(traffic: str, seeds: list[int], requests: int,
           marks: list[int]) -> dict:
    import jax

    import harness
    from oncilla_tpu import models as program_models

    # The family's model module, where its adapters look the programs up.
    conv_moe = sys.modules[program_models.ConvMoeConfig.__module__]
    with open(os.path.join(HERE, "traffic", f"{traffic}.json")) as f:
        spec = json.load(f)
    cfg = conv_moe.ConvMoeConfig.tiny()
    P = int(spec["engine"]["page_tokens"])
    buckets: collections.Counter = collections.Counter()
    contexts: collections.Counter = collections.Counter()
    padded: collections.Counter = collections.Counter()
    fused, prefill = (conv_moe.conv_decode_batch_step_jit,
                      conv_moe.conv_decode_page_jit)

    def count_fused(params, toks, metas, n_real, pool, table, *rest):
        buckets[(toks.shape[0], table.shape[1], pool[0].shape[0])] += 1
        return fused(params, toks, metas, n_real, pool, table, *rest)

    def count_prefill(params, toks, meta, ctx, *rest):
        contexts[int(meta[0]) // P] += 1
        padded[ctx[0].shape[3] // P] += 1
        return prefill(params, toks, meta, ctx, *rest)

    conv_moe.conv_decode_batch_step_jit = count_fused
    conv_moe.conv_decode_page_jit = count_prefill
    by_seed: dict = {}
    try:
        gen = harness.load_plugin("generators", spec["generator"])
        for seed in seeds:
            params = conv_moe.init_params(jax.random.key(seed), cfg)
            problems: list = []
            with harness.serving_stack(cfg, params, spec["engine"], "census",
                                       problems) as (engine, _):
                loop = harness.Loop(
                    engine, gen.schedule(seed, spec["params"], cfg.vocab))
                full, target = loop.clients, 0
                for clients, n in spec.get("warm", {}).get("ramp", []):
                    loop.clients = min(int(clients), full)
                    target += int(n)
                    loop.run_until(lambda: len(loop.done) >= target)
                loop.clients = full
                at_marks = {}
                for mark in sorted(set(marks + [requests])):
                    loop.run_until(lambda: len(loop.done) >= mark)
                    meta = engine.metrics_meta()
                    at_marks[mark] = {
                        "ticks": loop.ticks,
                        "tier_pages": meta["tier_pages"],
                        "prefix": meta["prefix"]}
                loop.drain()
                meta = engine.metrics_meta()
                by_seed[seed] = {
                    "at_requests": at_marks, "hops": meta["moves"]["hops"],
                    "tier_pages_peak": meta["tier_pages_peak"],
                    "prefix": meta["prefix"], "stalls": meta["stalls"],
                    "moe": meta["moe"], "carry": meta["carry"],
                    "batch_steps": meta["batch"]["steps"],
                    "reused_share": round(
                        sum(r.result.prefix_tokens_reused for r in loop.done)
                        / sum(r.prompt_len for r in loop.done), 4)}
            if problems:
                raise RuntimeError(f"seed {seed}: {problems}")
    finally:
        conv_moe.conv_decode_batch_step_jit = fused
        conv_moe.conv_decode_page_jit = prefill
    return {"fused_buckets": sorted([list(k), n] for k, n in buckets.items()),
            "prefill_context_pages": sorted(contexts.items()),
            "prefill_padded_pages": sorted(padded.items()),
            "by_seed": by_seed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--requests", type=int, default=700)
    ap.add_argument("--marks", default="")
    args = ap.parse_args(argv)
    out = census(args.traffic, [int(s) for s in args.seeds.split(",")],
                 args.requests,
                 [int(m) for m in args.marks.split(",") if m])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
