"""CPU tests of the per-layer metrics that read the serving tick's span tree
and the TTFT parts (``benchmark/layer_metrics/sched.*`` and
``prefill.chunk_wall_ms``): each reader on hand-made window deltas, and all of
them on the spans and counters a tiny engine really emits. A CPU run proves
names and arithmetic, never a time."""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_tick_anatomy", os.path.join(BENCH, "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def span(count, total_s):
    return {"count": count, "total_s": total_s}


# 50 ticks of 200 ms; 40 fused steps of 150 ms; 30 chunks of 100 ms.
SPANS = {
    "tick": span(50, 10.0),
    "tick.admit": span(50, 0.1), "tick.match": span(50, 0.2),
    "serve_prefill_chunk": span(30, 3.0), "tick.select": span(50, 0.1),
    "serve_batch_step": span(40, 6.0), "tick.finish": span(50, 0.3),
    "prefill.residency": span(30, 0.6), "prefill.dispatch": span(30, 1.7),
    "prefill.sync": span(10, 0.2), "prefill.ship": span(30, 0.3),
    "step.residency": span(40, 0.4), "step.pool": span(40, 2.0),
    "step.args": span(40, 0.8), "step.dispatch": span(40, 0.2),
    "step.sync": span(40, 0.8), "step.scatter": span(40, 1.6),
    "step.ship": span(30, 0.3), "step.publish": span(20, 0.4),
    "put": span(90, 0.5),
}
STATS = {"ttft": {"count": 50, "sum_s": 100.0,
                  "parts": {"queue_s": 0.5, "chunk_s": 40.0, "tail_s": 59.5,
                            "unseated_ticks": 12}}}
# what the parent commit hands a reader: the two spans and the counters it had
OLD_SPANS = {"serve_batch_step": span(40, 6.0), "put": span(90, 0.5)}
OLD_STATS = {"ttft": {"count": 50, "sum_s": 100.0, "hist": {}}}

READERS = {
    "sched.tick_wall_ms": 200.0,
    "sched.tick_unattributed_share": 3.0,       # 10.0 - 9.7 of 10.0
    "sched.host_share": 90.0,                   # 10.0 - 0.8 - 0.2 of 10.0
    "sched.step_build_ms": 80.0,                # 0.4 + 2.0 + 0.8 over 40
    "sched.step_sync_ms": 25.0,                 # 0.2 + 0.8 over 40
    "sched.ship_ms_per_page": 10.0,             # 0.3 + 0.3 over 30 + 30
    "prefill.chunk_wall_ms": 100.0,
    "sched.ttft_tail_share": 59.5,
    "sched.ttft_queue_ms": 10.0,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_hand_made_spans_and_counters(harness, name):
    read = harness.load_plugin("layer_metrics", name).read
    assert read(STATS, SPANS, None, {}) == pytest.approx(READERS[name])
    # nothing to read, as at a commit without these spans and counters:
    # nothing returned, nothing raised
    assert read({}, {}, None, {}) is None
    if name != "prefill.chunk_wall_ms":     # that span is older than its reader
        assert read(OLD_STATS, OLD_SPANS, None, {}) is None


def test_benchmark_json_lists_the_nine_after_the_eleven(harness):
    per_layer = harness._read_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]
    names = [m["name"] for m in per_layer]
    assert len(names) >= 20 and set(READERS) <= set(names[11:])
    by_name = {m["name"]: m for m in per_layer}
    assert by_name["prefill.chunk_wall_ms"]["layer"] == by_name["step.device_ms"]["layer"]
    for name in READERS:
        if name.startswith("sched."):
            assert by_name[name]["layer"] == by_name["sched.tick_ms"]["layer"]
        assert by_name[name]["source"] in ("program_span", "program_counter")


def test_all_nine_read_something_from_a_tiny_engines_window(harness):
    """The names the readers ask for are the names the engine emits: a window
    of a tiny batched engine with a prefix cache, taken as the harness takes
    it (``span_totals`` and ``metrics_meta`` before and after)."""
    import oncilla_tpu as ocm
    from oncilla_tpu.models import LlamaConfig, init_params_host
    from oncilla_tpu.serving.engine import Request, ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.prefix import PrefixCache
    from oncilla_tpu.serving.tiers import TieredPageStore

    page = 8
    cfg = LlamaConfig.tiny()
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, ServingEngine.page_nbytes(cfg, page),
                            hot_capacity=48, warm_capacity=8,
                            stats=ServingStats("anatomy"))
    eng = ServingEngine(init_params_host(0, cfg), cfg, store,
                        PrefixCache(store, page), page_tokens=page, max_active=3,
                        max_batch=2, prefetch_workers=0, name="anatomy",
                        batched=True)
    rng = np.random.default_rng(7)
    base = rng.integers(1, cfg.vocab, 3 * page).tolist()
    try:
        stats0, spans0 = eng.metrics_meta(), harness.span_totals()
        for i, n in enumerate((5, 11, 2 * page, 3, page, 13)):
            eng.submit(Request(tenant=f"q{i}", max_new_tokens=10,
                               tokens=base + rng.integers(1, cfg.vocab, n).tolist()))
        ticks = 0
        while eng.queue or eng.active:
            eng._tick()
            ticks += 1
        stats = harness.delta(eng.metrics_meta(), stats0)
        spans = harness.delta(harness.span_totals(), spans0)
    finally:
        eng.close()
        store.close()
        ctx.tini()
    values = {name: harness.load_plugin("layer_metrics", name).read(
        stats, spans, None, {}) for name in READERS}
    assert all(v is not None for v in values.values()), values
    assert spans["tick"]["count"] == ticks
    assert 0 <= values["sched.tick_unattributed_share"] < 10
    assert 0 < values["sched.host_share"] <= 100
    assert 0 < values["sched.ttft_tail_share"] < 100
    assert values["sched.ttft_queue_ms"] > 0    # six requests, three places
    parts = stats["ttft"]["parts"]
    assert parts["queue_s"] + parts["chunk_s"] + parts["tail_s"] == pytest.approx(
        stats["ttft"]["sum_s"], abs=1e-5)
