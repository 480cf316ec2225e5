#!/usr/bin/env python3
"""Which shapes does a traffic mix reach? A CPU tool, for whoever writes a
mix's ``warm`` section: it runs the mix's schedule through the engine on a
tiny model with the mix's own ``engine`` section and counts the
(batch, pages, pool rows) buckets of the fused step and the context lengths
of the prefill program. Shapes follow token counts and capacities, not
widths, so the tiny model reaches the shapes the real one will. It counts;
it measures nothing.

A tool of the ``dense_gqa`` family: it builds the tiny ``LlamaConfig`` itself
and wraps the dense paged path's two programs. A family with other programs
brings a census of its own beside its warmer.

    JAX_PLATFORMS=cpu python3 benchmark/census.py --traffic agent-shared --seeds 1,2 --requests 300
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
    import oncilla_tpu.serving.engine as engine_mod
    from oncilla_tpu.models import LlamaConfig, llama

    with open(os.path.join(HERE, "traffic", f"{traffic}.json")) as f:
        spec = json.load(f)
    cfg = LlamaConfig(vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_hidden=128, max_seq=4096, dtype="float32")
    P = int(spec["engine"]["page_tokens"])
    buckets: collections.Counter = collections.Counter()
    contexts: collections.Counter = collections.Counter()
    fused, prefill = (engine_mod.paged_decode_batch_step_jit,
                      engine_mod.paged_decode_page_jit)

    def count_fused(params, toks, metas, pool_k, pool_v, table, *rest):
        buckets[(toks.shape[0], table.shape[1], pool_k.shape[0])] += 1
        return fused(params, toks, metas, pool_k, pool_v, table, *rest)

    def count_prefill(params, toks, meta, k_ctx, *rest):
        contexts[k_ctx.shape[3] // P] += 1
        return prefill(params, toks, meta, k_ctx, *rest)

    engine_mod.paged_decode_batch_step_jit = count_fused
    engine_mod.paged_decode_page_jit = count_prefill
    hops: dict = {}
    try:
        gen = harness.load_plugin("generators", spec["generator"])
        for seed in seeds:
            params = llama.init_params(jax.random.key(seed), cfg)
            problems: list = []
            with harness.serving_stack(cfg, params, spec["engine"], "census",
                                       problems) as (engine, _):
                loop = harness.Loop(
                    engine, gen.schedule(seed, spec["params"], cfg.vocab))
                loop.run_until(lambda: len(loop.done) >= requests)
                meta = engine.metrics_meta()
                hops[seed] = {"ticks": loop.ticks, "hops": meta["moves"]["hops"],
                              "tier_pages_peak": meta["tier_pages_peak"],
                              "stalls": meta["stalls"]}
                loop.drain()
            if problems:
                raise RuntimeError(f"seed {seed}: {problems}")
    finally:
        engine_mod.paged_decode_batch_step_jit = fused
        engine_mod.paged_decode_page_jit = prefill
    return {"fused_buckets": sorted([list(k), n] for k, n in buckets.items()),
            "prefill_context_pages": sorted(contexts.items()),
            "by_seed": hops}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--requests", type=int, default=300)
    args = ap.parse_args(argv)
    out = census(args.traffic, [int(s) for s in args.seeds.split(",")],
                 args.requests)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
