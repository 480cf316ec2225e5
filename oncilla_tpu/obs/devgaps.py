"""Why is the chip idle: device idle time by the host span it fell in.

The join of the two halves of one profiler trace. ``Tracer.span`` opens a
``jax.profiler.TraceAnnotation("ocm:<op>")``, so every program span is on the
profiler's clock, on the host thread that ran it; the device's plane
(``/device:TPU:<n>``) has one ``XLA Ops`` event per operation and one ``XLA
Modules`` event per program execution. Here the device's idle intervals (the
complement of the union of its ``XLA Ops`` intervals within the traced span)
are intersected with the *innermost* ``ocm:*`` annotation open on the host
thread that carries ``ocm:tick`` (the serving scheduler's); what falls under
no annotation is ``outside any span``. Each device program is counted under
the span that dispatched it: executions leave the host and reach one device
in the same order, so the k-th ``PJRT_LoadedExecutable_Execute`` of the
thread is the k-th ``XLA Modules`` event.

``python -m oncilla_tpu.obs gaps <trace dir or .xplane.pb[.gz]>`` prints the
table. Reads the trace with JAX's ``ProfileData`` alone, imported on use: the
rest of ``obs/`` stays stdlib-only.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re

OUTSIDE = "outside any span"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_EXECUTE = "PJRT_LoadedExecutable_Execute"


def load(path: str):
    """The trace as ``jax.profiler.ProfileData``: an ``.xplane.pb`` file,
    gzipped or not, or the newest one under a directory."""
    import jax.profiler

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    elif not os.path.isfile(path):
        raise FileNotFoundError(f"no such trace: {path}")
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return jax.profiler.ProfileData.from_file(path)


def idle_intervals(busy: list, lo: float, hi: float) -> list:
    """[lo, hi) less the union of the ``busy`` (start, end) intervals."""
    out, cur = [], lo
    for s, e in sorted(busy):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost(events: list) -> list:
    """One thread's nested (start, end, name) intervals as disjoint
    (start, end, name) segments, each named after the innermost one open."""
    out: list = []
    stack: list = []    # open intervals, outermost first: (end, name)
    cur = 0.0

    def close(until: float) -> None:
        nonlocal cur
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            if s > cur:
                out.append((cur, s, stack[-1][1]))
            e = min(e, stack[-1][0])    # a child never outlives its parent
        cur = max(cur, s) if stack else s
        stack.append((e, name))
    close(float("inf"))
    return out


def attribute(idle: list, segments: list) -> dict:
    """Nanoseconds of the ``idle`` intervals under each segment's name; what
    no segment covers goes to ``OUTSIDE``. Both lists sorted and disjoint."""
    out: dict = {}
    i = 0
    for s, e in idle:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, name = segments[j]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            j += 1
        if e - s > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (e - s) - covered
    return out


def _events(line) -> list:
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
            for ev in line.events]


def _name_at(segments: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return segments[i][2] if i >= 0 and t < segments[i][1] else OUTSIDE


def gaps(path: str, chip: int = 0) -> dict:
    """Idle seconds of device ``chip`` by host span, and the device programs
    each span dispatched. ``by_span`` is sorted, most idle first."""
    data = load(path)
    device = host = None
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) == chip:
            device = {line.name: _events(line) for line in plane.lines}
        elif plane.name == "/host:CPU":
            host = [(line.name, _events(line)) for line in plane.lines]
    modules = (device or {}).get("XLA Modules", [])
    ops = (device or {}).get("XLA Ops") or modules
    if not ops or not host:
        raise ValueError(f"{path}: no operations of /device:TPU:{chip}, or no "
                         "/host:CPU plane")
    # The scheduler's thread: the one with the most ticks (with no tick at
    # all, as in a trace of the memory plane alone, the most ocm:*
    # annotations).
    thread, events = max(host, key=lambda t: (
        sum(n == "ocm:tick" for _, _, n in t[1]),
        sum(n.startswith("ocm:") for _, _, n in t[1])))
    segments = innermost([(s, e, n[4:]) for s, e, n in events
                          if n.startswith("ocm:")])
    lo = min(s for s, _, _ in ops + modules)
    hi = max(e for _, e, _ in ops + modules)
    idle = idle_intervals([(s, e) for s, e, _ in ops], lo, hi)
    by_span = {name: {"idle_s": ns / 1e9, "programs": {}}
               for name, ns in attribute(idle, segments).items()}
    launches = sorted(s for s, _, n in events if n.startswith(_EXECUTE))
    starts = [a for a, _, _ in segments]
    for t, (_, _, program) in zip(launches, sorted(modules)):
        rec = by_span.setdefault(_name_at(segments, starts, t),
                                 {"idle_s": 0.0, "programs": {}})
        program = re.sub(r"\(\d+\)$", "", program)
        rec["programs"][program] = rec["programs"].get(program, 0) + 1
    idle_s = sum(e - s for s, e in idle) / 1e9
    rows = [{"span": name, "share": rec["idle_s"] / idle_s if idle_s else 0.0,
             **rec} for name, rec in by_span.items()]
    rows.sort(key=lambda r: -r["idle_s"])
    return {"chip": chip, "thread": thread, "span_s": (hi - lo) / 1e9,
            "idle_s": idle_s, "by_span": rows,
            "unmatched_programs": abs(len(launches) - len(modules))}


def render(result: dict, programs: int = 3) -> str:
    """The ``obs gaps`` table: idle seconds and share by span, and the
    programs each span dispatched most."""
    idle, span = result["idle_s"], result["span_s"]
    lines = [
        f"/device:TPU:{result['chip']}: idle {idle:.4f} s of {span:.4f} s "
        f"traced ({100 * idle / span:.1f} %); host thread {result['thread']}; "
        f"{result['unmatched_programs']} program(s) unmatched",
        f"{'span':<22} {'idle_s':>9} {'share':>7}  programs dispatched inside",
    ]
    for r in result["by_span"]:
        top = sorted(r["programs"].items(), key=lambda kv: -kv[1])
        more = f", +{len(top) - programs} more" if len(top) > programs else ""
        lines.append(
            f"{r['span']:<22} {r['idle_s']:>9.4f} {100 * r['share']:>6.1f}%  "
            + ", ".join(f"{n} x{c}" for n, c in top[:programs]) + more)
    return "\n".join(lines) + "\n"
