"""CPU tests of ``benchmark/layer_metrics/tiers.scrub_dispatches_per_page.py``:
the ratio on hand-made counters, silence where the program has no ``frees``
counters (the parent of the PR that brought them) or freed nothing, the reader
on what a store really counts, and the entry in ``BENCHMARK.json`` found by
name with the reader where the harness looks for it. A CPU run proves names
and arithmetic, never a time."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "tiers.scrub_dispatches_per_page"
CELLS = [
    "internlm2-1.8b.agent-shared", "mistral-7b-v0.1-d16.sessions-overcommit",
    "xing4.0-29b-a4b-d6.decode-heavy", "ling-3.0-flash-vl-ep4-d7.state-decode",
    "mistral-7b-v0.1-d16.sessions-fit", "laguna-s-2.1-ep4-d5.mixed-lengths",
]


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_scrub", os.path.join(ROOT, "benchmark", "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def read(harness):
    return harness.load_plugin("layer_metrics", NAME).read


@pytest.mark.parametrize("stats, want", [
    # a session's 40 pages in one group: one dispatch
    ({"frees": {"pages": 40, "calls": 1, "scrub_dispatches": 1}}, 0.025),
    # a dispatch a page (a page a call), and the old path's power-of-two cut
    ({"frees": {"pages": 12, "calls": 12, "scrub_dispatches": 12}}, 1.0),
    ({"frees": {"pages": 10, "calls": 10, "scrub_dispatches": 40}}, 4.0),
    # pages of tiers below HOT alone: freed, and nothing scrubbed
    ({"frees": {"pages": 3, "calls": 1, "scrub_dispatches": 0}}, 0.0),
    # a window that freed nothing, or a program without the counters
    ({"frees": {"pages": 0, "calls": 0, "scrub_dispatches": 0}}, None),
    ({"frees": {}}, None),
    ({"moves": {"promote": 1, "demote": 2, "hops": {}}, "stall_s": 0.1}, None),
    ({}, None),
])
def test_dispatches_over_pages_with_and_without_the_counters(read, stats, want):
    got = read(stats, {}, None, {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_reads_what_a_store_counts_over_a_window(harness, read):
    """The counters as the harness takes them: a snapshot before and one
    after, the difference read."""
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = 12 << 10
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=64 * pb))
    store = TieredPageStore(ctx, pb, hot_capacity=60, warm_capacity=4,
                            stats=ServingStats("scrub"))
    data = np.ones(pb, np.uint8)
    try:
        pages = [store.alloc_page(data) for _ in range(26)]
        assert read(store.stats.snapshot(), {}, None, {}) is None
        store.free_page(pages[0])
        before = store.stats.snapshot()
        assert read(before, {}, None, {}) == 1.0
        store.free_pages(pages[1:21])           # 20 pages: one padded group
        for page in pages[21:]:                 # 5 pages, a call each
            store.free_page(page)
        win = harness.delta(store.stats.snapshot(), before)
        assert win["frees"] == {"pages": 25, "calls": 6, "scrub_dispatches": 6}
        assert read(win, {}, None, {}) == pytest.approx(6 / 25)
    finally:
        store.close()
        ctx.tini()


def test_the_entry_is_found_by_name_with_the_six_cells(harness):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "tier store (serving/tiers.py)",
                     "moves": "itl_ms_p95", "workloads": CELLS}
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] == "tiers.stall_ms_per_tok"}
    assert "itl_ms_p95" in {m["name"] for m in bench["end_to_end"]}
    assert [w["name"] for w in bench["workloads"]] == CELLS
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       f"{NAME}.py"))
    # every cell's traced run asks the reader
    for cell in CELLS:
        assert NAME in [m["name"] for m in harness.load_cell(cell).per_layer]
