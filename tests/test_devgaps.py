"""``obs/devgaps.py``: device idle time by the host span it fell in.

The interval arithmetic on hand-made planes, then the whole join on a slice
of ``internlm2-1.8b.agent-shared`` traced on a v5e with the tick's span tree
(``tests/fixtures/``, recorded by ``benchmark/run.py --trace 1 --keep-trace
DIR --trace-seconds 0.4``), against the benchmark's own reduction of the
same file."""

from __future__ import annotations

import importlib.util
import os
from types import SimpleNamespace

import pytest

from oncilla_tpu.obs import devgaps
from oncilla_tpu.obs.__main__ import main as obs_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "tests", "fixtures",
                       "agent-shared.tick-anatomy.v5e.xplane.pb.gz")


def test_idle_is_the_complement_of_the_union():
    idle = devgaps.idle_intervals([(10, 20), (15, 30), (50, 60), (52, 55)], 0, 100)
    assert idle == [(0, 10), (30, 50), (60, 100)]
    assert devgaps.idle_intervals([], 5, 9) == [(5, 9)]
    assert devgaps.idle_intervals([(0, 100)], 0, 100) == []
    # operations that run past the traced span leave nothing after it
    assert devgaps.idle_intervals([(90, 120)], 0, 100) == [(0, 90)]


def test_innermost_span_names_every_moment_once():
    segs = devgaps.innermost([
        (0, 100, "tick"), (10, 40, "step"), (20, 30, "step.pool"),
        (50, 60, "tick.finish"), (200, 300, "tick"), (210, 400, "runaway")])
    assert segs == [
        (0, 10, "tick"), (10, 20, "step"), (20, 30, "step.pool"),
        (30, 40, "step"), (40, 50, "tick"), (50, 60, "tick.finish"),
        (60, 100, "tick"), (200, 210, "tick"), (210, 300, "runaway")]
    # disjoint and in order, so the intersection below is one sweep
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))


def test_idle_lands_on_the_span_it_fell_in_and_the_rest_outside():
    segs = devgaps.innermost([(0, 100, "tick"), (10, 40, "step.pool")])
    got = devgaps.attribute([(5, 20), (35, 50), (90, 130), (150, 160)], segs)
    assert got == {"tick": 5 + 10 + 10, "step.pool": 10 + 5,
                   devgaps.OUTSIDE: 30 + 10}
    assert devgaps.attribute([], segs) == {}
    assert devgaps.attribute([(0, 7)], []) == {devgaps.OUTSIDE: 7}


def _line(name, events):
    return SimpleNamespace(name=name, events=[
        SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
        for s, e, n in events])


def test_the_join_on_a_hand_made_trace(monkeypatch):
    """Two ticks; the device works 100-200 and 600-650 (ns) of 100-1000."""
    ms = 1_000_000
    host = [
        _line("worker", [(0, 2000 * ms, "ocm:get")] * 3),
        _line("python3", [
            (0, 500 * ms, "ocm:tick"), (50 * ms, 400 * ms, "ocm:serve_batch_step"),
            (60 * ms, 90 * ms, "ocm:step.pool"),
            (70 * ms, 71 * ms, "PJRT_LoadedExecutable_Execute linkage"),
            (300 * ms, 380 * ms, "ocm:step.sync"),
            (550 * ms, 900 * ms, "ocm:tick"),
            (560 * ms, 561 * ms, "PJRT_LoadedExecutable_Execute linkage"),
            (950 * ms, 951 * ms, "PJRT_LoadedExecutable_Execute linkage"),
        ]),
    ]
    device = [
        _line("XLA Modules", [(100 * ms, 200 * ms, "jit_stack(123)"),
                              (600 * ms, 650 * ms, "jit_step(456)"),
                              (990 * ms, 1000 * ms, "jit_fill(7)")]),
        _line("XLA Ops", [(100 * ms, 150 * ms, "%fusion.1"),
                          (150 * ms, 200 * ms, "%copy.2"),
                          (600 * ms, 650 * ms, "%fusion.3"),
                          (990 * ms, 1000 * ms, "%fusion.4")]),
    ]
    data = SimpleNamespace(planes=[
        SimpleNamespace(name="/device:TPU:0", lines=device),
        SimpleNamespace(name="/device:TPU:1", lines=[]),
        SimpleNamespace(name="/host:CPU", lines=host)])
    monkeypatch.setattr(devgaps, "load", lambda path: data)
    got = devgaps.gaps("anything")
    assert got["thread"] == "python3"
    assert got["span_s"] == pytest.approx(0.9) and got["idle_s"] == pytest.approx(0.74)
    rows = {r["span"]: r for r in got["by_span"]}
    # idle 200-600: step 200-300, sync 300-380, step 380-400, tick 400-500,
    # outside 500-550, tick 550-600; idle 650-990: tick 650-900, outside 900-990
    assert rows["serve_batch_step"]["idle_s"] == pytest.approx(0.12)
    assert rows["step.sync"]["idle_s"] == pytest.approx(0.08)
    assert rows["tick"]["idle_s"] == pytest.approx(0.1 + 0.05 + 0.25)
    assert rows[devgaps.OUTSIDE]["idle_s"] == pytest.approx(0.05 + 0.09)
    assert sum(r["idle_s"] for r in got["by_span"]) == pytest.approx(got["idle_s"])
    assert sum(r["share"] for r in got["by_span"]) == pytest.approx(1.0)
    # each program under the span that dispatched it, in launch order
    assert rows["step.pool"] == {"span": "step.pool", "idle_s": 0.0, "share": 0.0,
                                 "programs": {"jit_stack": 1}}
    assert rows["tick"]["programs"] == {"jit_step": 1}
    assert rows[devgaps.OUTSIDE]["programs"] == {"jit_fill": 1}
    assert got["unmatched_programs"] == 0
    assert [r["span"] for r in got["by_span"]][0] == "tick"     # most idle first
    table = devgaps.render(got)
    assert "idle 0.7400 s of 0.9000 s traced (82.2 %)" in table
    assert "step.pool" in table and "jit_stack x1" in table
    with pytest.raises(ValueError):
        devgaps.gaps("anything", chip=1)


def _trace_reduce():
    spec = importlib.util.spec_from_file_location(
        "bench_trace_reduce_for_devgaps",
        os.path.join(ROOT, "benchmark", "trace_reduce.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_join_on_the_trace_recorded_on_the_chip(capsys):
    got = devgaps.gaps(FIXTURE)
    reduced = _trace_reduce().reduce(FIXTURE, chips=1)
    named = sum(r["idle_s"] for r in got["by_span"])
    assert named == pytest.approx(reduced["idle_s"], rel=0.01)
    assert got["span_s"] == pytest.approx(reduced["span_s"], rel=1e-6)
    rows = {r["span"]: r for r in got["by_span"]}
    assert rows.get(devgaps.OUTSIDE, {"share": 0.0})["share"] < 0.05
    # the scheduler's thread and its tree are what the idle time falls under
    assert any(name.startswith("step.") for name in rows)
    assert sum(sum(r["programs"].values()) for r in got["by_span"]) == sum(
        v["count"] for v in reduced["programs"].values()) - got["unmatched_programs"]
    # the fused step is dispatched from step.dispatch and nowhere else
    step = "jit_paged_decode_batch_step_jit"
    assert [r["span"] for r in got["by_span"] if step in r["programs"]] == [
        "step.dispatch"]
    assert obs_main(["gaps", FIXTURE]) == 0
    out = capsys.readouterr().out
    assert out.splitlines()[0].startswith("/device:TPU:0: idle ")
    assert "programs dispatched inside" in out and "step.pool" in out
    assert obs_main(["gaps", os.path.join(ROOT, "tests", "fixtures", "analysis")]) == 2
