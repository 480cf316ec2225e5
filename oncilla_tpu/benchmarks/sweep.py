"""Size-doubling one-sided bandwidth sweep.

The measurement *shape* of the reference's integration benchmark
(/root/reference/test/ocm_test.c:323-402): allocate one region, then for each
size 64 B, 128 B, ... max — a separate WRITE pass and a separate READ pass of
N iterations each, reporting per-size GB/s. Two flavors:

- :func:`size_sweep` drives the public ``put``/``get`` path on any handle
  kind (local host/device, or remote kinds through a cluster control plane) —
  the controller-orchestrated view, including protocol overhead.
- :func:`spmd_ring_sweep` times the in-mesh fabric itself: every device
  ships its chunk to its ring neighbor simultaneously (all ICI links active),
  iterated inside one jitted program so dispatch cost is amortized — the
  shape used for the GB/s-per-chip-vs-line-rate target (BASELINE.md).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from oncilla_tpu.benchmarks._util import fence as _force
from oncilla_tpu.core.kinds import OcmKind
from oncilla_tpu.utils.debug import printd


@dataclass
class SweepPoint:
    nbytes: int
    iters: int
    # None = leg skipped (write capped by write_max_bytes, or the amortized
    # read unavailable for this size/kind).
    write_gbps: float | None
    read_gbps: float
    # Dispatch-amortized routed device read (k reads in one compiled
    # program, ops/pallas_ici.pallas_read_rows_loop) — the figure that
    # shows the DMA engine when per-op dispatch latency dominates.
    read_amortized_gbps: float | None = None


@dataclass
class SweepResult:
    label: str
    points: list[SweepPoint] = field(default_factory=list)
    # Sizes dropped because the sweep's wall-clock budget ran out —
    # recorded, never silent (a truncated sweep must not read as a
    # complete one).
    dropped: list[int] = field(default_factory=list)
    # Per-leg failures/skips ("amortized:<nbytes>" → reason) — a leg that
    # silently reads as "unavailable" would hide a regression in the
    # routed-DMA path the sweep exists to evidence.
    errors: dict[str, str] = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "label": self.label,
            "points": [vars(p) for p in self.points],
            "dropped": list(self.dropped),
            "errors": dict(self.errors),
        }


def _doubling_sizes(min_bytes: int, max_bytes: int) -> list[int]:
    sizes, n = [], min_bytes
    while n <= max_bytes:
        sizes.append(n)
        n *= 2
    return sizes


def _read_amortized_gbps(
    ctx, h, nbytes: int, k: int, errors: dict[str, str]
) -> float | None:
    """Routed DMA read rate with dispatch amortized over ``k`` reads in one
    compiled program. None when the extent doesn't qualify for the routed
    path (unaligned / too small / not on real TPU) — the per-op leg is then
    the only read figure, honestly. A *failure* (as opposed to
    ineligibility) is recorded in ``errors`` so the banked JSON names the
    cause instead of silently falling back to the per-op leg."""
    # Eligibility lookups stay OUTSIDE the try: an API drift here (arena
    # attribute rename, handle shape change) should fail the test suite
    # loudly, not read as "leg unavailable".
    arena = ctx.device_arenas[h.device_index or 0]
    start = h.extent.offset
    if not arena._dma_eligible(start, nbytes):
        return None
    from oncilla_tpu.ops.pallas_ici import pallas_read_rows_loop

    buf = arena.buffer
    try:
        out = pallas_read_rows_loop(buf, start, nbytes, k)  # compile + warm
        _force(out[:8])
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            out = pallas_read_rows_loop(buf, start, nbytes, k)
            _force(out[:8])
            best = max(best, nbytes * k / (time.perf_counter() - t0) / 1e9)
        return best
    except Exception as exc:  # noqa: BLE001 — an optional leg must never
        # abort the sweep and discard the points already measured (e.g. an
        # HBM OOM compiling the k-unrolled loop against a >2 GiB arena).
        errors[f"amortized:{nbytes}"] = f"{type(exc).__name__}: {exc}"
        printd("amortized read leg failed at %d B: %r", nbytes, exc)
        return None


def size_sweep(
    ctx,
    kind: OcmKind = OcmKind.LOCAL_HOST,
    min_bytes: int = 64,
    max_bytes: int = 1 << 20,
    iters: int = 8,
    device_index: int = 0,
    budget_s: float | None = None,
    write_max_bytes: int | None = None,
    amortize_k: int = 0,
    amortize_min_bytes: int = 32 << 20,
    descending: bool = False,
) -> SweepResult:
    """Alloc one ``max_bytes`` region of ``kind``; per size, a write pass then
    a read pass of ``iters`` one-sided ops each (ocm_test.c:362-402 shape).
    With ``budget_s``, sizes whose turn comes after the budget is spent are
    skipped and listed in ``result.dropped`` (per-size compiles plus
    GB-scale writes over a slow host link can cost minutes).

    Leg semantics for LOCAL_DEVICE: the write leg stages host bytes into
    the arena extent (host→device link on the path), while the read leg
    lands in the app-side buffer — which for a
    TPU-native consumer is a device-resident ``jax.Array``, so it measures
    the on-device extent read, NOT a device→host transfer. The legs are
    deliberately asymmetric because the app's buffers live on opposite
    sides of the link.
    ``descending`` visits sizes largest-first so that under budget
    pressure the big (usually judged) points bank before the budget runs
    out; ``result.points`` stays sorted ascending either way.

    ``write_max_bytes`` skips the write leg above that size (recorded as
    ``None``): at GB scale the leg measures the host link, not the
    arena. ``amortize_k`` > 0 adds
    a third leg for LOCAL_DEVICE sizes ≥ ``amortize_min_bytes``: the
    routed DMA read timed as ``k`` reads inside one compiled program, so
    per-dispatch latency divides out — this is the leg that shows the
    engine rate the per-op read leg hides.
    """
    h = ctx.alloc(max_bytes, kind, device_index=device_index) \
        if kind == OcmKind.LOCAL_DEVICE else ctx.alloc(max_bytes, kind)
    res = SweepResult(label=f"size_sweep:{kind.name}")
    rng = np.random.default_rng(0xB0)
    t_start = time.perf_counter()
    sizes = _doubling_sizes(min_bytes, max_bytes)
    if descending:
        sizes = sizes[::-1]
    try:
        for nbytes in sizes:
            if (budget_s is not None
                    and time.perf_counter() - t_start > budget_s):
                res.dropped.append(nbytes)
                continue
            write_gbps: float | None = None
            if write_max_bytes is None or nbytes <= write_max_bytes:
                data = rng.integers(0, 256, nbytes, dtype=np.uint8)
                ctx.put(h, data)  # warm caches / compile this size
                _force(ctx.get(h, 8))
                t0 = time.perf_counter()
                for _ in range(iters):
                    ctx.put(h, data)
                _force(ctx.get(h, 8))  # fence the last lazy write
                wt = time.perf_counter() - t0
                write_gbps = nbytes * iters / wt / 1e9

            out = ctx.get(h, nbytes)
            _force(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = ctx.get(h, nbytes)
            _force(out)
            rt = time.perf_counter() - t0

            amortized: float | None = None
            if (amortize_k > 0 and nbytes >= amortize_min_bytes
                    and kind == OcmKind.LOCAL_DEVICE):
                # Re-check the budget: the leg costs a fresh k-unrolled
                # compile plus 3·k·nbytes of reads, which must not
                # overshoot past the stage bound ("seconds bounds the
                # whole stage") and starve whatever runs after the sweep.
                if (budget_s is not None
                        and time.perf_counter() - t_start > budget_s):
                    res.errors[f"amortized:{nbytes}"] = "skipped: budget"
                else:
                    amortized = _read_amortized_gbps(
                        ctx, h, nbytes, amortize_k, res.errors
                    )
            res.points.append(
                SweepPoint(
                    nbytes=nbytes,
                    iters=iters,
                    write_gbps=write_gbps,
                    read_gbps=nbytes * iters / rt / 1e9,
                    read_amortized_gbps=amortized,
                )
            )
    finally:
        ctx.free(h)
    res.points.sort(key=lambda p: p.nbytes)
    res.dropped.sort()
    return res


def spmd_ring_sweep(
    mesh=None,
    min_bytes: int = 1 << 10,
    max_bytes: int = 1 << 24,
    iters: int = 16,
    arena_bytes: int | None = None,
) -> SweepResult:
    """All-links sweep on the SPMD arena fabric: per size, ``iters`` ring
    shifts (every chip sends+receives ``nbytes`` simultaneously) timed
    end-to-end; reports per-chip GB/s (bytes sent per chip / time)."""
    from oncilla_tpu.parallel import spmd_arena as sa
    from oncilla_tpu.parallel.mesh import node_mesh

    mesh = mesh if mesh is not None else node_mesh()
    if arena_bytes is None:
        arena_bytes = max_bytes
    if arena_bytes < max_bytes:
        raise ValueError(
            f"arena_bytes ({arena_bytes}) must hold the largest chunk "
            f"(max_bytes={max_bytes})"
        )
    arena = sa.make_arena(mesh, arena_bytes)
    res = SweepResult(label=f"spmd_ring_sweep:{mesh.devices.size}dev")
    for nbytes in _doubling_sizes(min_bytes, max_bytes):
        arena = sa.ring_shift(arena, 0, nbytes, mesh=mesh)  # compile
        _force(arena[0, :8])
        t0 = time.perf_counter()
        for _ in range(iters):
            arena = sa.ring_shift(arena, 0, nbytes, mesh=mesh)
        _force(arena[0, :8])  # fences the whole chain (data dependency)
        dt = time.perf_counter() - t0
        gbps = nbytes * iters / dt / 1e9
        # One ring shift moves nbytes out of (and into) every chip; per-chip
        # GB/s is the per-size figure BASELINE.md asks to compare to line rate.
        res.points.append(
            SweepPoint(nbytes=nbytes, iters=iters, write_gbps=gbps, read_gbps=gbps)
        )
    return res


def main() -> None:
    import argparse
    import json

    import oncilla_tpu as ocm

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["local", "ring"], default="local")
    ap.add_argument("--kind", default="LOCAL_DEVICE")
    ap.add_argument("--min-bytes", type=int, default=64)
    ap.add_argument("--max-bytes", type=int, default=1 << 24)
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()

    if args.mode == "ring":
        res = spmd_ring_sweep(
            min_bytes=args.min_bytes, max_bytes=args.max_bytes, iters=args.iters
        )
    else:
        cfg = ocm.OcmConfig(
            host_arena_bytes=2 * args.max_bytes,
            device_arena_bytes=2 * args.max_bytes,
        )
        ctx = ocm.ocm_init(cfg)
        res = size_sweep(
            ctx,
            OcmKind[args.kind],
            min_bytes=args.min_bytes,
            max_bytes=args.max_bytes,
            iters=args.iters,
        )
        ocm.ocm_tini(ctx)
    print(json.dumps(res.as_dict()))


if __name__ == "__main__":
    main()
