#!/usr/bin/env python3
"""Which shapes does a traffic mix reach on the ``swa_gqa_moe`` family's
paged path? The family's twin of ``census_kda_latent.py`` (a CPU tool, for
whoever writes a mix's ``warm`` section and sizes its HOT tier): it runs the
mix's schedule through the engine on the tiny ``SwaMoeConfig`` (its window
set to the published 512) with the mix's own ``engine`` section and counts
the (batch, then a kind: pages, pool rows) buckets of the fused step, by the
phase they fell in (before the mix's ``warm.requests`` had finished, which a
run spends before its window; after; and in the drain), the context
lengths the page program is handed (and, a kind, the padded page counts
the family's join hands it), the most pages the store held at once, what that would
have been had no window-kind page been dropped, and the window and kv
counters. Shapes follow token counts and capacities, not widths. It counts;
it measures nothing.

    JAX_PLATFORMS=cpu python3 benchmark/census_swa_moe.py --traffic mixed-lengths --requests 150
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
           window: int = 512) -> dict:
    import jax

    import harness
    from oncilla_tpu import models as program_models

    # The family's model module, where its adapters look the programs up.
    swa_moe = sys.modules[program_models.SwaMoeConfig.__module__]
    with open(os.path.join(HERE, "traffic", f"{traffic}.json")) as f:
        spec = json.load(f)
    cfg = swa_moe.SwaMoeConfig.tiny(sliding_window=window)
    P = int(spec["engine"]["page_tokens"])
    buckets = {phase: collections.Counter()
               for phase in ("warm", "window", "drain")}
    phase = ["warm"]
    contexts: collections.Counter = collections.Counter()
    padded: collections.Counter = collections.Counter()
    rows: list = [set(), set()]
    fused, prefill = (swa_moe.swa_decode_batch_step_jit,
                      swa_moe.swa_decode_page_jit)

    def count_fused(params, toks, metas, n_real, pool, tables, *rest):
        shape = (toks.shape[0], tables[0].shape[1], pool[0].shape[0],
                 tables[1].shape[1], pool[2].shape[0])
        buckets[phase[0]][shape] += 1
        rows[0].add(shape[2])
        rows[1].add(shape[4])
        return fused(params, toks, metas, n_real, pool, tables, *rest)

    def count_prefill(params, toks, meta, ctx, *rest):
        contexts[int(meta[0]) // P] += 1
        padded[(ctx[0].shape[3] // P, ctx[2].shape[3] // P)] += 1
        return prefill(params, toks, meta, ctx, *rest)

    swa_moe.swa_decode_batch_step_jit = count_fused
    swa_moe.swa_decode_page_jit = count_prefill
    by_seed: dict = {}
    try:
        gen = harness.load_plugin("generators", spec["generator"])
        for seed in seeds:
            params = swa_moe.init_params(jax.random.key(seed), cfg)
            problems: list = []
            with harness.serving_stack(cfg, params, spec["engine"], "census",
                                       problems) as (engine, _):
                loop = harness.Loop(
                    engine, gen.schedule(seed, spec["params"], cfg.vocab))
                live = undropped = listed = 0
                warm_requests = int(spec["warm"].get("requests", 0))
                while len(loop.done) < requests:
                    phase[0] = ("warm" if len(loop.done) < warm_requests
                                else "window")
                    loop.tick()
                    live = max(live, len(engine.store.pages))
                    # A page a kind at every page boundary, had none gone.
                    undropped = max(undropped, sum(
                        len(engine.kinds) * (s.pos // P)
                        for s in engine.active))
                    listed = max([listed] + [
                        sum(e.kind == 1 for e in s.entries)
                        for s in engine.active])
                meta = engine.metrics_meta()
                by_seed[seed] = {
                    "ticks": loop.ticks, "hops": meta["moves"]["hops"],
                    "tier_pages_peak": meta["tier_pages_peak"],
                    "live_pages_peak": live,
                    "live_pages_peak_without_the_drop": undropped,
                    "window_pages_listed_peak": listed,
                    "stalls": meta["stalls"], "moe": meta["moe"],
                    "window": meta["window"], "kv": meta["kv"],
                    "batch_steps": meta["batch"]["steps"],
                    "prefill_chunks": meta["batch"]["prefill_chunks"]}
                phase[0] = "drain"
                loop.drain()
            if problems:
                raise RuntimeError(f"seed {seed}: {problems}")
    finally:
        swa_moe.swa_decode_batch_step_jit = fused
        swa_moe.swa_decode_page_jit = prefill
    return {"fused_buckets": {
                phase: sorted([list(k), n] for k, n in found.items())
                for phase, found in buckets.items()},
            "pool_rows": [sorted(r) for r in rows],
            "prefill_context_pages": sorted(contexts.items()),
            "prefill_padded_pages": sorted(
                [list(k), n] for k, n in padded.items()),
            "by_seed": by_seed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--requests", type=int, default=150)
    ap.add_argument("--window", type=int, default=512,
                    help="sliding_window of the tiny config (the published "
                         "one: drops follow it, not widths)")
    args = ap.parse_args(argv)
    out = census(args.traffic, [int(s) for s in args.seeds.split(",")],
                 args.requests, args.window)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
