#!/usr/bin/env python3
"""Which shapes does a traffic mix reach on the latent family's paged path?
The ``latent_moe_hc`` family's twin of ``census.py`` (a CPU tool, for
whoever writes a mix's ``warm`` section): it runs the mix's schedule through
the engine on the tiny ``LatentMoeConfig`` with the mix's own ``engine``
section and counts the (batch, pages, pool rows) buckets of the fused step
and the context lengths of the page program. Shapes follow token counts and
capacities, not widths. It counts; it measures nothing.

    JAX_PLATFORMS=cpu python3 benchmark/census_latent_moe.py --traffic decode-heavy --seeds 1,2 --requests 150
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

    # The family's model module, where the engine looks its programs up.
    latent_moe = sys.modules[program_models.LatentMoeConfig.__module__]
    with open(os.path.join(HERE, "traffic", f"{traffic}.json")) as f:
        spec = json.load(f)
    cfg = latent_moe.LatentMoeConfig.tiny()
    P = int(spec["engine"]["page_tokens"])
    buckets: collections.Counter = collections.Counter()
    contexts: collections.Counter = collections.Counter()
    fused, prefill = (latent_moe.latent_decode_batch_step_jit,
                      latent_moe.latent_decode_page_jit)

    def count_fused(params, toks, metas, n_real, pool, table, *rest):
        buckets[(toks.shape[0], table.shape[1], pool.shape[0])] += 1
        return fused(params, toks, metas, n_real, pool, table, *rest)

    def count_prefill(params, toks, meta, ctx, *rest):
        contexts[ctx.shape[3] // P] += 1
        return prefill(params, toks, meta, ctx, *rest)

    latent_moe.latent_decode_batch_step_jit = count_fused
    latent_moe.latent_decode_page_jit = count_prefill
    by_seed: dict = {}
    try:
        gen = harness.load_plugin("generators", spec["generator"])
        for seed in seeds:
            params = latent_moe.init_params(jax.random.key(seed), cfg)
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
                    "batch_steps": meta["batch"]["steps"]}
                loop.drain()
            if problems:
                raise RuntimeError(f"seed {seed}: {problems}")
    finally:
        latent_moe.latent_decode_batch_step_jit = fused
        latent_moe.latent_decode_page_jit = prefill
    return {"fused_buckets": sorted([list(k), n] for k, n in buckets.items()),
            "prefill_context_pages": sorted(contexts.items()),
            "by_seed": by_seed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--requests", type=int, default=150)
    args = ap.parse_args(argv)
    out = census(args.traffic, [int(s) for s in args.seeds.split(",")],
                 args.requests)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
