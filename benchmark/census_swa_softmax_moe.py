#!/usr/bin/env python3
"""Which shapes does a traffic mix reach on the ``swa_gqa_softmax_moe``
family's paged path, and how many pages does HOT have to hold? A CPU tool,
for whoever writes the mix's ``warm`` section and sizes its HOT tier:
``census_swa_moe.py``'s count (fused-step buckets by phase, page-program
contexts and their padded page counts, the most pages the store held at
once, what that would have been had no window-kind page been dropped, the
window and kv counters) run on the tiny Mellum-shaped config
(``SwaMoeConfig.tiny_softmax``) with its window set to the published 1024.
Shapes and page counts follow token counts and capacities, not widths. It
counts; it measures nothing.

    JAX_PLATFORMS=cpu python3 benchmark/census_swa_softmax_moe.py --traffic file-context --requests 64
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def census(traffic: str, seeds: list[int], requests: int,
           window: int = 1024) -> dict:
    from oncilla_tpu import models as program_models

    SwaMoeConfig = program_models.SwaMoeConfig
    spec = importlib.util.spec_from_file_location(
        "benchmark_census_swa_moe", os.path.join(HERE, "census_swa_moe.py"))
    laguna = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(laguna)
    # census_swa_moe builds its config as SwaMoeConfig.tiny(sliding_window=
    # ...); for the length of the count that is the Mellum shape.
    tiny = vars(SwaMoeConfig)["tiny"]
    SwaMoeConfig.tiny = vars(SwaMoeConfig)["tiny_softmax"]
    try:
        return laguna.census(traffic, seeds, requests, window)
    finally:
        SwaMoeConfig.tiny = tiny


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--window", type=int, default=1024,
                    help="sliding_window of the tiny config (the published "
                         "one: drops follow it, not widths)")
    args = ap.parse_args(argv)
    out = census(args.traffic, [int(s) for s in args.seeds.split(",")],
                 args.requests, args.window)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
