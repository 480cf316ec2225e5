"""CPU tests of ``benchmark/layer_metrics/sched.pool_reused_share.py``: the
share on hand-made counters, silence where the program has no such counters
(the parent of the PR that brought them), and the entry in ``BENCHMARK.json``."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "sched.pool_reused_share"


@pytest.fixture(scope="module")
def read():
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{NAME}.py")
    spec = importlib.util.spec_from_file_location("bench_pool_reused_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("stats, want", [
    ({"pool": {"rows_reused": 950, "rows_written": 50, "rebuilds": 1}}, 95.0),
    ({"pool": {"rows_reused": 0, "rows_written": 8, "rebuilds": 1}}, 0.0),
    # no fused step in the window, or a program without the counters
    ({"pool": {"rows_reused": 0, "rows_written": 0, "rebuilds": 0}}, None),
    ({"batch": {"steps": 40, "size_sum": 240}}, None),
])
def test_share_of_rows_found_on_the_device(read, stats, want):
    got = read(stats, {}, None, {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_entry_reads_the_programs_own_snapshot(read):
    from oncilla_tpu.serving.metrics import ServingStats

    stats = ServingStats("t")
    stats.note_pool(written=5, rebuilt=True)
    stats.note_pool(reused=5)
    stats.note_pool(reused=5, written=1)
    snap = stats.snapshot()
    assert snap["pool"] == {"rows_reused": 10, "rows_written": 6, "rebuilds": 1}
    assert read(snap, {}, None, {}) == pytest.approx(62.5)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "scheduler (serving/engine.py)",
                     "moves": "itl_ms_p95"}
