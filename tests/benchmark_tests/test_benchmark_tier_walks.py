"""CPU tests of ``benchmark/layer_metrics/tiers.walks_per_page_placed.py``:
the ratio on hand-made counters, silence where the program has no ``places``
counters (the parent of the PR that brought them) or placed nothing, the
reader on what a store really counts over a window, and the entry in
``BENCHMARK.json`` found BY NAME behind the accepted entries (a leading
slice: a later PR appends behind it and breaks nothing here). A CPU run
proves names and arithmetic, never a time."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "tiers.walks_per_page_placed"
# The accepted per-layer entries, in their places (later PRs append behind).
ACCEPTED = [
    "entry.window_compiles", "sched.tick_ms", "sched.batch_fill",
    "prefix.reused_share", "tiers.stall_ms_per_tok",
    "tiers.moved_MiB_per_tok", "memplane.op_ms_per_tick", "step.device_ms",
    "step.roofline_share", "dma.roofline_share", "device.idle_share",
    "sched.tick_wall_ms", "sched.tick_unattributed_share", "sched.host_share",
    "sched.step_build_ms", "sched.step_sync_ms", "sched.ship_ms_per_page",
    "prefill.chunk_wall_ms", "sched.ttft_tail_share", "sched.ttft_queue_ms",
    "sched.pool_reused_share", "moe.experts_touched_share",
    "moe.step_roofline_share", "prefill.page_roofline_share",
    "kda.step_roofline_share", "kda.page_roofline_share",
    "moe.held_touched_share", "carry.seats_kept_share",
    "swa.step_roofline_share", "swa.page_roofline_share", "kv.held_share",
    "itl.p95_ms", "itl.tail_ticks", "itl.tail_chunk_share",
    "itl.tail_build_share", "itl.tail_device_share",
    "itl.tail_scatter_share", "itl.tail_finish_share", "ttft.tail_ticks",
    "ttft.tail_unseated_share", "ttft.tail_own_chunk_share",
    "sched.pool_dispatches_per_step", "tiers.scrub_dispatches_per_page",
    "conv.step_roofline_share", "conv.page_roofline_share",
    "prefix.carry_reused_share", "prefix.snapshot_ms_per_page",
]
ACCEPTED_CELLS = [
    "internlm2-1.8b.agent-shared", "mistral-7b-v0.1-d16.sessions-overcommit",
    "xing4.0-29b-a4b-d6.decode-heavy", "ling-3.0-flash-vl-ep4-d7.state-decode",
    "mistral-7b-v0.1-d16.sessions-fit", "laguna-s-2.1-ep4-d5.mixed-lengths",
    "lfm2-24b-a2b-d10.agent-prefix",
]


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_walks", os.path.join(ROOT, "benchmark", "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def read(harness):
    return harness.load_plugin("layer_metrics", NAME).read


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("stats, want", [
    # every page under HOT's high mark: placed by the counts alone
    ({"places": {"pages": 118, "walks": 0}}, 0.0),
    # a victim sought a placement, and the store that walked for every
    # question (eight walks a page placed)
    ({"places": {"pages": 40, "walks": 40}}, 1.0),
    ({"places": {"pages": 5, "walks": 40}}, 8.0),
    # a sweep now and then
    ({"places": {"pages": 70, "walks": 7}}, 0.1),
    # a window that placed nothing, or a program without the counters
    ({"places": {"pages": 0, "walks": 3}}, None),
    ({"places": {}}, None),
    ({"frees": {"pages": 4, "calls": 1, "scrub_dispatches": 1}}, None),
    ({}, None),
])
def test_walks_over_pages_with_and_without_the_counters(read, stats, want):
    got = read(stats, {}, None, {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_reads_what_a_store_counts_over_a_window(harness, read):
    """The counters as the harness takes them: a snapshot before and one
    after, the difference read. HOT ample reads 0; a HOT under the pages
    placed reads the victims sought."""
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = 4 << 10
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=40 * pb))
    data = np.ones(pb, np.uint8)
    ample = TieredPageStore(ctx, pb, hot_capacity=32, warm_capacity=4,
                            stats=ServingStats("ample"))
    try:
        assert read(ample.stats.snapshot(), {}, None, {}) is None
        pages = [ample.alloc_page(data) for _ in range(5)]
        before = ample.stats.snapshot()
        pages += [ample.alloc_page(data) for _ in range(12)]
        ample.free_pages(pages)
        win = harness.delta(ample.stats.snapshot(), before)
        assert win["places"] == {"pages": 12, "walks": 0}
        assert read(win, {}, None, {}) == 0.0
    finally:
        ample.close()
    tight = TieredPageStore(ctx, pb, hot_capacity=4, warm_capacity=64,
                            high_pct=100, low_pct=100,
                            stats=ServingStats("tight"))
    try:
        for _ in range(4):
            tight.alloc_page(data)
        before = tight.stats.snapshot()
        for _ in range(6):                    # HOT at capacity: a victim each
            tight.alloc_page(data)
        win = harness.delta(tight.stats.snapshot(), before)
        assert win["places"] == {"pages": 6, "walks": 6}
        assert read(win, {}, None, {}) == 1.0
    finally:
        tight.close()
        ctx.tini()


def test_the_entry_is_found_by_name_behind_the_accepted_entries(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    # Every cell places pages, but the entry lists six. Without a list it
    # would come last in the tiny cells that older tests of this directory
    # build and hold by position (test_benchmark_latent_moe.py), and
    # test_benchmark_conv_moe.py holds that no entry but its own four names
    # `lfm2-24b-a2b-d10.agent-prefix`: a PR that mends those tests can add
    # that cell or drop the list. A later PR appends its cells behind these.
    cells = entry.pop("workloads")
    assert cells[:6] == ACCEPTED_CELLS[:6]
    assert set(cells) <= {w["name"] for w in bench["workloads"]}
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "tier store (serving/tiers.py)",
                     "moves": "out_tok_s"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names.index(NAME) >= len(ACCEPTED) and len(set(names)) == len(names)
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] == "tiers.stall_ms_per_tok"}
    assert "out_tok_s" in {m["name"] for m in bench["end_to_end"]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       f"{NAME}.py"))


def test_nothing_accepted_moved(bench):
    assert [w["name"] for w in bench["workloads"]][
        :len(ACCEPTED_CELLS)] == ACCEPTED_CELLS
    assert bench["run_seconds"] == 45
    assert [(e["name"], e["bound"]) for e in bench["end_to_end"]] == [
        ("out_tok_s", 0.05), ("ttft_ms_p90", 0.1), ("itl_ms_p95", 0.08),
        ("setup_s", 0.1)]
    # no accepted metric's list of cells grew or shrank with this entry
    for name, cells in (("tiers.scrub_dispatches_per_page", 6),
                        ("itl.p95_ms", 6), ("dma.roofline_share", 2),
                        ("prefix.snapshot_ms_per_page", 1)):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(m["workloads"]) == cells


@pytest.mark.parametrize("cell", ACCEPTED_CELLS[:6])
def test_every_listed_cells_traced_run_asks_the_reader(harness, cell):
    assert NAME in [m["name"] for m in harness.load_cell(cell).per_layer]
