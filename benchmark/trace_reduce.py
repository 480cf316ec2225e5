"""From the profiler's ``.xplane.pb`` to the device numbers every traced
run reports: busy and idle time, device time by program (jitted function)
and by operation (XLA op or Pallas kernel), and the idle gaps by the program
the device was waiting for. Reads the file with nothing but JAX
(``jax.profiler.ProfileData``); checked on the recorded trace under
``fixtures/`` by ``tests/benchmark_tests``.

A TPU's plane is ``/device:TPU:<n>``. Its ``XLA Modules`` line has one event
per program execution, named after the jitted function; its ``XLA Ops`` line
has one event per operation inside them. Busy is the union of the ``XLA
Ops`` intervals (of the ``XLA Modules`` intervals where a trace has no ops
line); idle is the traced span less that.
"""

from __future__ import annotations

import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Operations that only hold others (a scan's body runs inside its ``while``):
# they count toward busy time like any interval, not toward time by operation.
CONTAINERS = frozenset({"while", "conditional", "call"})


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program_name(event_name: str) -> str:
    """``jit_step(1234567890)`` -> ``jit_step``: the fingerprint changes
    with the shapes, the program's name does not."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.123 = ...`` or ``fusion.123`` -> ``fusion``: instances of
    one kind of operation add up."""
    name = event_name.lstrip("%").split(" ", 1)[0]
    return re.sub(r"[.\d]+$", "", name) or name


def union_s(intervals: list) -> float:
    """Seconds covered by [start_ns, end_ns) intervals, overlaps once."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def _by_name(events: list, key) -> dict:
    out: dict = {}
    for name, s, e in events:
        rec = out.setdefault(key(name), {"count": 0, "total_s": 0.0})
        rec["count"] += 1
        rec["total_s"] += (e - s) / 1e9
    return out


def _read_plane(plane) -> dict:
    lines = {}
    for line in plane.lines:
        lines[line.name] = [
            (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events
        ]
    return lines


def _gaps_before(modules: list, floor_ns: float = 1e5) -> dict:
    """Idle time by the program that ended the gap: what the device was
    waiting for the host to dispatch."""
    out: dict = {}
    end = None
    for name, s, e in sorted(modules, key=lambda m: m[1]):
        if end is not None and s - end > floor_ns:
            key = "before " + program_name(name)
            out[key] = out.get(key, 0.0) + (s - end) / 1e9
        end = e if end is None else max(end, e)
    return out


def program(reduced: dict, fragment: str) -> tuple:
    """(executions, device seconds) of the programs whose name holds
    ``fragment``, summed over shapes."""
    hits = [v for k, v in reduced["programs"].items() if fragment in k]
    return sum(v["count"] for v in hits), sum(v["total_s"] for v in hits)


def reduce(path: str, chips: int = 1) -> dict:
    """The reduction. Times are seconds; per-chip quantities are averaged
    over the ``chips`` device planes with the lowest numbers."""
    import jax.profiler

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            planes.append((int(m.group(1)), plane))
    planes = [p for _, p in sorted(planes, key=lambda t: t[0])][:chips]
    if not planes:
        raise ValueError(
            f"{path}: no device plane among {[p.name for p in data.planes]}")
    busy, span, programs, ops, gaps = 0.0, 0.0, {}, {}, {}
    line_names = []
    for plane in planes:
        lines = _read_plane(plane)
        line_names.append({k: len(v) for k, v in lines.items()})
        modules = lines.get(MODULES_LINE, [])
        op_events = lines.get(OPS_LINE) or modules
        if not op_events:
            continue
        busy += union_s([(s, e) for _, s, e in op_events])
        every = op_events + modules
        span += (max(e for _, _, e in every)
                 - min(s for _, s, _ in every)) / 1e9
        for target, events, key in ((programs, modules, program_name),
                                    (ops, op_events, op_name)):
            for name, rec in _by_name(events, key).items():
                if target is ops and name in CONTAINERS:
                    continue
                t = target.setdefault(name, {"count": 0, "total_s": 0.0})
                t["count"] += rec["count"]
                t["total_s"] += rec["total_s"]
        for name, s in _gaps_before(modules).items():
            gaps[name] = gaps.get(name, 0.0) + s
    n = len(planes)
    top = sorted(ops.items(), key=lambda kv: -kv[1]["total_s"])
    return {
        "chips": n,
        "busy_s": busy / n,
        "span_s": span / n,
        "idle_s": (span - busy) / n,
        "programs": programs,
        "ops": ops,
        "top_ops": [[k, v["total_s"] / n] for k, v in top[:10]],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
        "lines": line_names,
    }
