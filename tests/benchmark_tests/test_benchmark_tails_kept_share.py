"""CPU tests of ``benchmark/layer_metrics/sched.tails_kept_share.py``: the
share on hand-made counters, silence where the program has no such counters
(the parent of the PR that brought them), the reader on the program's own
snapshot, and the harness finding the reader under the metric's name."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "sched.tails_kept_share"


@pytest.fixture(scope="module")
def read():
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{NAME}.py")
    spec = importlib.util.spec_from_file_location("bench_tails_kept_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("stats, want", [
    ({"tails": {"seats_kept": 940, "seats_written": 60}}, 94.0),
    ({"tails": {"seats_kept": 0, "seats_written": 16}}, 0.0),
    ({"tails": {"seats_kept": 16, "seats_written": 0}}, 100.0),
    # no fused step in the window, or a program without the counters
    ({"tails": {"seats_kept": 0, "seats_written": 0}}, None),
    ({"pool": {"rows_reused": 10, "rows_written": 6, "rebuilds": 1}}, None),
    ({}, None),
])
def test_share_of_seats_the_stack_already_held(read, stats, want):
    got = read(stats, {}, None, {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_reads_the_programs_own_snapshot(read):
    from oncilla_tpu.serving.metrics import ServingStats

    stats = ServingStats("t")
    assert read(stats.snapshot(), {}, None, {}) is None
    stats.note_tails(written=4)
    stats.note_tails(kept=4)
    stats.note_tails(kept=3, written=1)
    snap = stats.snapshot()
    assert snap["tails"] == {"seats_kept": 7, "seats_written": 5}
    assert read(snap, {}, None, {}) == pytest.approx(100.0 * 7 / 12)


def test_the_harness_finds_the_reader_by_name_and_an_entry_is_found_by_name():
    """The reader lies where ``harness.load_plugin`` looks for a metric of
    its name. The entry in ``BENCHMARK.json`` waits for a ``benchmark`` PR
    (PERF.md, Open questions): when it comes it is looked up by name, at
    whatever place in ``per_layer`` it stands."""
    spec = importlib.util.spec_from_file_location(
        "bench_harness_tails", os.path.join(ROOT, "benchmark", "harness.py"))
    harness = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = harness    # dataclasses look their module up
    spec.loader.exec_module(harness)
    stats = {"tails": {"seats_kept": 3, "seats_written": 1}}
    assert harness.load_plugin("layer_metrics", NAME).read(
        stats, {}, None, {}) == pytest.approx(75.0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entries in ([], [{"name": NAME, "unit": "%", "better": "higher",
                             "source": "program_counter",
                             "layer": "scheduler (serving/engine.py)",
                             "moves": "itl_ms_p95"}])
    # no `workloads` list: every cell steps, and every cell reports what
    # the metric moves
    moved = next(m for m in bench["end_to_end"] if m["name"] == "itl_ms_p95")
    assert "workloads" not in moved
