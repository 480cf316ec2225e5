"""CPU tests of the readers of the tails' anatomy (``benchmark/tail_hist.py``
and the eleven ``benchmark/layer_metrics/`` files that came with it): the
shared reduction on hand-made histograms, each reader with and without its
keys, the entries in ``BENCHMARK.json``, and all eleven on what a tiny engine
really files, through a copy of the benchmark that lists a tiny cell. A CPU
run proves names and arithmetic, never a time."""

from __future__ import annotations

import importlib.util
import json
import os
import runpy
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

INF = float("inf")
CELLS = [
    "internlm2-1.8b.agent-shared", "mistral-7b-v0.1-d16.sessions-overcommit",
    "xing4.0-29b-a4b-d6.decode-heavy", "ling-3.0-flash-vl-ep4-d7.state-decode",
    "mistral-7b-v0.1-d16.sessions-fit", "laguna-s-2.1-ep4-d5.mixed-lengths",
]
# name -> (unit, better, moves), in the order they were appended
NEW = {
    "itl.p95_ms": ("ms", "lower", "itl_ms_p95"),
    "itl.tail_ticks": ("count", "lower", "itl_ms_p95"),
    "itl.tail_chunk_share": ("%", "lower", "itl_ms_p95"),
    "itl.tail_build_share": ("%", "lower", "itl_ms_p95"),
    "itl.tail_device_share": ("%", "higher", "itl_ms_p95"),
    "itl.tail_scatter_share": ("%", "lower", "itl_ms_p95"),
    "itl.tail_finish_share": ("%", "lower", "itl_ms_p95"),
    "ttft.tail_ticks": ("count", "lower", "ttft_ms_p90"),
    "ttft.tail_unseated_share": ("%", "lower", "ttft_ms_p90"),
    "ttft.tail_own_chunk_share": ("%", "higher", "ttft_ms_p90"),
    "sched.pool_dispatches_per_step": ("count", "lower", "itl_ms_p95"),
}
# The accepted per-layer entries, in their places, and the cells of those
# that list any: what this PR found and may not move.
ACCEPTED = [
    "entry.window_compiles", "sched.tick_ms", "sched.batch_fill",
    "prefix.reused_share", "tiers.stall_ms_per_tok", "tiers.moved_MiB_per_tok",
    "memplane.op_ms_per_tick", "step.device_ms", "step.roofline_share",
    "dma.roofline_share", "device.idle_share", "sched.tick_wall_ms",
    "sched.tick_unattributed_share", "sched.host_share", "sched.step_build_ms",
    "sched.step_sync_ms", "sched.ship_ms_per_page", "prefill.chunk_wall_ms",
    "sched.ttft_tail_share", "sched.ttft_queue_ms", "sched.pool_reused_share",
    "moe.experts_touched_share", "moe.step_roofline_share",
    "prefill.page_roofline_share", "kda.step_roofline_share",
    "kda.page_roofline_share", "moe.held_touched_share",
    "carry.seats_kept_share", "swa.step_roofline_share",
    "swa.page_roofline_share", "kv.held_share",
]
ACCEPTED_CELLS = {
    "prefix.reused_share": CELLS[:1], "dma.roofline_share": CELLS[:2],
    "moe.experts_touched_share": CELLS[2:3], "moe.step_roofline_share": CELLS[2:3],
    "prefill.page_roofline_share": CELLS[2:3],
    "kda.step_roofline_share": CELLS[3:4], "kda.page_roofline_share": CELLS[3:4],
    "moe.held_touched_share": CELLS[3:4], "carry.seats_kept_share": CELLS[3:4],
    "swa.step_roofline_share": CELLS[5:], "swa.page_roofline_share": CELLS[5:],
    "kv.held_share": CELLS[5:],
}


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_gap_anatomy")


@pytest.fixture(scope="module")
def lib():
    return runpy.run_path(os.path.join(BENCH, "tail_hist.py"))


def gaps(count, sum_s, ticks, outside_s=0.0, split=(0.5, 0.1, 0.2, 0.1, 0.05)):
    """A bucket of ``itl.hist``: the phases as shares of ``sum_s``, what is
    left of it ``sched_s``."""
    chunk, build, device, scatter, finish = (f * sum_s for f in split)
    return {"count": count, "sum_s": sum_s, "ticks": ticks,
            "outside_s": outside_s, "chunk_s": chunk, "build_s": build,
            "device_s": device, "scatter_s": scatter, "finish_s": finish,
            "sched_s": sum_s - chunk - build - device - scatter - finish}


def firsts(count, sum_s, ticks, unseated_ticks, own_chunk_s, queue_s=0.0):
    return {"count": count, "sum_s": sum_s, "ticks": ticks,
            "unseated_ticks": unseated_ticks, "own_chunk_s": own_chunk_s,
            "queue_s": queue_s}


# 100 gaps: the slowest 5 are the top bucket's 2 and 3 of the middle one's 8.
ITL_HIST = {
    0.02: gaps(90, 1.5, 90, 0.01, split=(0.0, 0.2, 0.6, 0.1, 0.05)),
    0.1: gaps(8, 0.6, 10),
    0.8: gaps(2, 1.2, 6, outside_s=20.0),
}
# 20 first tokens: the slowest 2 are the top bucket.
TTFT_TAIL = {1.0: firsts(18, 9.0, 180, 0, 4.5),
             4.0: firsts(2, 6.0, 100, 60, 1.5, queue_s=0.2)}
STATS = {
    "itl": {"count": 100, "sum_s": 3.3, "outside_s": 20.01, "hist": ITL_HIST},
    "ttft": {"count": 20, "sum_s": 15.2, "hist": {},
             "parts": {"queue_s": 0.2, "chunk_s": 6.0, "tail_s": 9.0,
                       "unseated_ticks": 60},
             "tail_hist": TTFT_TAIL},
    "pool_dispatches": {"group_writes": 30, "gathers": 2, "rows_carried": 99},
    "batch": {"steps": 80},
}
# what a program from before this PR hands a reader
OLD_STATS = {
    "ttft": {"count": 20, "sum_s": 15.2, "hist": {},
             "parts": {"queue_s": 0.2, "chunk_s": 6.0, "tail_s": 9.0,
                       "unseated_ticks": 60}},
    "pool": {"rows_reused": 10, "rows_written": 6, "rebuilds": 1},
    "batch": {"steps": 80},
}
LOW = 0.1 / 2 ** 0.25
WANT = {
    "itl.p95_ms": 1e3 * (LOW + (0.1 - LOW) * 5 / 8),
    "itl.tail_ticks": (6 + 10 * 3 / 8) / 5,
    "itl.tail_chunk_share": 50.0,
    "itl.tail_build_share": 10.0,
    "itl.tail_device_share": 20.0,
    "itl.tail_scatter_share": 10.0,
    "itl.tail_finish_share": 5.0,
    "ttft.tail_ticks": 50.0,
    "ttft.tail_unseated_share": 60.0,
    "ttft.tail_own_chunk_share": 25.0,
    "sched.pool_dispatches_per_step": 0.4,
}


# -- the shared reduction -----------------------------------------------------------


@pytest.mark.parametrize("hist, share, want", [
    # everything in one bucket: the slowest 5 % are 5 % of it
    ({0.1: gaps(40, 2.0, 60)}, 0.05, {"count": 2, "sum_s": 0.1, "ticks": 3}),
    # whole buckets from the top, the boundary bucket pro rata
    (ITL_HIST, 0.05, {"count": 5, "sum_s": 1.2 + 0.6 * 3 / 8,
                      "ticks": 6 + 10 * 3 / 8, "outside_s": 20.0}),
    # the boundary on a bucket's edge: that bucket whole, the next not at all
    (ITL_HIST, 0.10, {"count": 10, "sum_s": 1.8, "ticks": 16}),
    # everything
    (ITL_HIST, 1.0, {"count": 100, "sum_s": 3.3, "ticks": 106}),
    # fewer entries than the share takes one of: a part of the top one
    ({0.1: gaps(1, 0.09, 1), INF: gaps(1, 300.0, 4)}, 0.05,
     {"count": 0.1, "sum_s": 30.0, "ticks": 0.4}),
    # buckets a window's delta left empty, and keys that went through JSON
    ({"0.1": gaps(4, 0.3, 4), "0.8": gaps(0, 0.0, 0), "0.02": gaps(0, 0, 0)},
     0.5, {"count": 2, "sum_s": 0.15, "ticks": 2}),
    ({}, 0.05, None), (None, 0.05, None), ({0.1: gaps(0, 0.0, 0)}, 0.05, None),
])
def test_slowest_share_of_a_histogram_with_parts(lib, hist, share, want):
    got = lib["slowest"](hist, share)
    if want is None:
        assert got is None
        return
    assert {k: got[k] for k in want} == pytest.approx(want)
    # a bucket's parts stay its parts
    assert sum(got[f"{p}_s"] for p in ("chunk", "build", "device", "scatter",
                                        "finish", "sched")) == pytest.approx(
        got["sum_s"])


@pytest.mark.parametrize("hist, q, want", [
    (ITL_HIST, 0.95, LOW + (0.1 - LOW) * 5 / 8),
    # inside the lowest bucket that holds something: from its own lower edge
    (ITL_HIST, 0.45, 0.02 / 2 ** 0.25 * (1 + (2 ** 0.25 - 1) / 2)),
    # a neighbour below that holds something is the lower edge
    ({0.09: gaps(10, 0.8, 10), 0.1: gaps(10, 0.95, 10)}, 0.75, 0.095),
    # beyond the last bound there is no edge to reach for: the bucket's mean
    ({0.1: gaps(1, 0.09, 1), INF: gaps(3, 900.0, 3)}, 0.95, 300.0),
    ({}, 0.95, None),
])
def test_quantile_is_interpolated_in_its_bucket(lib, hist, q, want):
    got = lib["quantile"](hist, q)
    assert got == (pytest.approx(want) if want is not None else None)


# -- each reader --------------------------------------------------------------------


@pytest.mark.parametrize("name", list(NEW))
def test_reader_with_and_without_its_keys(harness, name):
    read = harness.load_plugin("layer_metrics", name).read
    assert read(STATS, {}, None, {}) == pytest.approx(WANT[name])
    # nothing to read, as at the commit before: nothing returned or raised
    assert read({}, {}, None, {}) is None
    assert read(OLD_STATS, {}, None, {}) is None
    # the keys there and nothing filed under them (no token in the window)
    empty = {"itl": {"count": 0, "sum_s": 0, "outside_s": 0, "hist": {}},
             "ttft": dict(OLD_STATS["ttft"], tail_hist={}),
             "pool_dispatches": {"group_writes": 0, "gathers": 0},
             "batch": {"steps": 0}}
    assert read(empty, {}, None, {}) is None


def test_the_five_shares_leave_sched_what_is_left_of_the_tail(harness):
    shares = [harness.load_plugin("layer_metrics", f"itl.tail_{p}_share").read(
        STATS, {}, None, {}) for p in ("chunk", "build", "device", "scatter",
                                       "finish")]
    assert all(0 <= s <= 100 for s in shares) and sum(shares) == pytest.approx(95)


# -- the entries --------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", list(NEW))
def test_entry_is_found_by_name_with_the_six_cells(bench_json, name):
    (entry,) = [m for m in bench_json["per_layer"] if m["name"] == name]
    unit, better, moves = NEW[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter",
                     "layer": "scheduler (serving/engine.py)", "moves": moves,
                     "workloads": CELLS}
    assert moves in {m["name"] for m in bench_json["end_to_end"]}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", f"{name}.py"))


def test_no_accepted_entry_list_or_position_changed(bench_json):
    per_layer = bench_json["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + len(NEW)] == list(NEW)
    assert {m["name"]: m["workloads"] for m in per_layer[:len(ACCEPTED)]
            if "workloads" in m} == ACCEPTED_CELLS
    assert [w["name"] for w in bench_json["workloads"]] == CELLS
    assert [(m["name"], m["bound"]) for m in bench_json["end_to_end"]] == [
        ("out_tok_s", 0.05), ("ttft_ms_p90", 0.1), ("itl_ms_p95", 0.08),
        ("setup_s", 0.1)]
    assert bench_json["run_seconds"] == 45


# -- all eleven on what a tiny engine files, through a copy -------------------------


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark whose ``BENCHMARK.json`` lists a tiny cell,
    appended to the six of every new entry."""
    tmp = tmp_path_factory.mktemp("bench_gap_copy")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (tmp / "benchmark/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "family": "dense_gqa"}))
    (tmp / "benchmark/traffic/tiny-mix.json").write_text(json.dumps(
        {"generator": "lognormal_turns"}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny", "source": "tests", "reduced": [],
                         "file": "benchmark/configs/tiny.json", "why": "tests"})
    b["workloads"].append({"name": "tiny.tiny-mix", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1, "why": "tests"})
    for m in b["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny.tiny-mix")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


def test_all_eleven_read_a_tiny_engines_window_through_a_copy(tiny_copy):
    import oncilla_tpu as ocm
    from oncilla_tpu.models import LlamaConfig, init_params_host
    from oncilla_tpu.serving.engine import Request, ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.tiers import TieredPageStore

    h = load(str(tiny_copy / "benchmark/harness.py"), "bench_harness_gap_copy")
    cell = h.load_cell("tiny.tiny-mix")
    mine = [m for m in cell.per_layer if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        reader = h.load_plugin("layer_metrics", m["name"])
        assert reader.__file__.startswith(str(tiny_copy))
        if m["name"] != "sched.pool_dispatches_per_step":
            assert reader._lib["slowest"].__code__.co_filename.startswith(
                str(tiny_copy))
    # a cell of the six has them too, behind what it had
    old = [m["name"] for m in h.load_cell(CELLS[2]).per_layer]
    assert old[-len(NEW):] == list(NEW) and "moe.step_roofline_share" in old

    page = 8
    cfg = LlamaConfig.tiny()
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, ServingEngine.page_nbytes(cfg, page),
                            hot_capacity=64, warm_capacity=8,
                            stats=ServingStats("gaps"))
    eng = ServingEngine(init_params_host(0, cfg), cfg, store, None,
                        page_tokens=page, max_active=4, max_batch=2,
                        prefetch_workers=0, name="gaps")
    rng = np.random.default_rng(11)

    def offer(lengths, first):
        for i, n in enumerate(lengths, first):
            eng.submit(Request(tenant=f"q{i}", max_new_tokens=10,
                               tokens=rng.integers(1, cfg.vocab, n).tolist()))

    try:
        # as the harness takes a window: after a warm-up, with requests in
        # flight at both ends, metrics_meta() before and after
        offer((5, 19, 26), 0)
        for _ in range(6):
            eng._tick()
        stats0 = eng.metrics_meta()
        offer((11, 33, 3, 17, 24), 3)
        ticks = 0
        while eng.queue or len(eng.active) > 1:
            eng._tick()
            ticks += 1
        stats = h.delta(eng.metrics_meta(), stats0)
    finally:
        eng.close()
        store.close()
        ctx.tini()
    values = {m["name"]: h.load_plugin("layer_metrics", m["name"]).read(
        stats, {}, None, {}) for m in mine}
    assert all(v is not None for v in values.values()), values
    itl = stats["itl"]
    assert 0 < itl["count"] == sum(b["count"] for b in itl["hist"].values())
    shares = [values[f"itl.tail_{p}_share"]
              for p in ("chunk", "build", "device", "scatter", "finish")]
    assert all(0 <= s <= 100 for s in shares) and sum(shares) <= 100 + 1e-9
    assert values["itl.tail_ticks"] >= 1 and values["ttft.tail_ticks"] >= 1
    # four places on two seats, prompts of whole pages among them
    assert 0 < values["ttft.tail_unseated_share"] < 100
    assert 0 <= values["ttft.tail_own_chunk_share"] <= 100
    assert values["itl.p95_ms"] > 0
    assert 0 < values["sched.pool_dispatches_per_step"] <= 2
    # the 95th gap lies where the histogram says the gaps are
    bounds = sorted(b for b, v in itl["hist"].items() if v["count"] > 0)
    assert bounds[0] / 2 ** 0.25 <= values["itl.p95_ms"] / 1e3 <= bounds[-1]
