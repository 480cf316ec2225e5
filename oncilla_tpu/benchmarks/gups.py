"""GUPS — giga-updates-per-second random access over the arena fabric.

BASELINE.md config 4 (no reference analogue): measure how fast randomly
addressed words can be updated, (a) within one chip's HBM arena and (b)
across the mesh, where every update targets a random word on a random chip
and rides ICI. TPU-idiomatic formulation: updates are batched scatter-adds
inside one jitted ``fori_loop`` (no per-update dispatch), and the cross-chip
flavor routes each batch with ``lax.all_to_all`` under ``shard_map`` — each
source device draws ``batch // D`` random target words *per destination
device*, so destinations are uniform and shapes stay static.

Updates are ``+1`` on a uint32 table, so correctness is checkable:
``table.sum() == total_updates`` (duplicate indices accumulate).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from oncilla_tpu.benchmarks._util import fence as _fence
from oncilla_tpu.parallel.mesh import NODE_AXIS, arena_sharding, node_mesh


@partial(jax.jit, donate_argnums=0, static_argnums=(1, 2, 3, 4, 5))
def _gups_single_run(table, steps: int, batch: int, words: int, seed: int,
                     method: str):
    def body(i, t):
        key = jax.random.fold_in(jax.random.key(seed), i)
        idx = jax.random.randint(key, (batch,), 0, words, dtype=jnp.int32)
        if method == "bincount":
            # Histogram formulation: XLA lowers bincount via sort/segment
            # machinery, which can beat the serialized scatter on TPU for
            # dense batches; same semantics (+1 per drawn index).
            return t + jnp.bincount(idx, length=words).astype(jnp.uint32)
        return t.at[idx].add(jnp.uint32(1))

    return jax.lax.fori_loop(0, steps, body, table)


def gups_single(
    words: int = 1 << 20,
    batch: int = 1 << 14,
    steps: int = 64,
    seed: int = 0,
    device=None,
    method: str = "scatter",
) -> dict:
    """Single-chip GUPS on a ``words``-word uint32 HBM table. ``method``
    picks the update lowering ("scatter" or "bincount"); both are exact."""
    def fresh():
        t = jnp.zeros((words,), dtype=jnp.uint32)
        return jax.device_put(t, device) if device is not None else t

    # Warm up with the SAME static args (steps is a static argnum — a
    # different value would recompile inside the timed region).
    _fence(_gups_single_run(fresh(), steps, batch, words, seed, method))
    table = fresh()
    _fence(table)
    t0 = time.perf_counter()
    table = _gups_single_run(table, steps, batch, words, seed, method)
    _fence(table)
    dt = time.perf_counter() - t0
    updates = steps * batch
    total = int(np.asarray(table).astype(np.uint64).sum())
    return {
        "mode": f"single:{method}",
        "gups": updates / dt / 1e9,
        "updates": updates,
        "seconds": dt,
        "table_sum": total,  # == updates (duplicates accumulate)
    }


def gups_single_best(
    words: int = 1 << 20,
    batch: int = 1 << 14,
    steps: int = 64,
    seed: int = 0,
) -> dict:
    """Measure both lowerings, verify conservation on each, keep the best
    (the engine sweet spot differs by backend/generation)."""
    best = None
    for method in ("scatter", "bincount"):
        r = gups_single(words=words, batch=batch, steps=steps, seed=seed,
                        method=method)
        if r["table_sum"] != r["updates"]:
            continue  # wrong results are not publishable
        if best is None or r["gups"] > best["gups"]:
            best = r
    if best is None:
        raise RuntimeError("no GUPS method produced conserved updates")
    return best


# -- handle/arena flavor: the oncilla number ------------------------------
#
# BASELINE config 4 says "random remote-access over ICI via ocm handles";
# the flavors above measure XLA scatter on a standalone table. Here the
# table IS an OcmAlloc extent inside an SpmdIciPlane
# arena row — the same (rank, device, offset) handle-addressed HBM the
# one-sided fabric serves. What the timed program does, precisely: slice
# the extent out of the (donated) arena row, apply ``steps`` batched
# update rounds, write the result back through the extent — the
# slice/bitcast entry+exit is ON the timed path once per run, amortized
# over the rounds rather than paid per round (its per-round form cost
# ~40% of the rate in the r5 first light, and per-round write-back is
# observationally identical inside one jit program anyway). Conservation
# is verified by reading the table back *through the handle*
# (plane.get_as), proving the updates landed in handle-addressable
# memory; what distinguishes this flavor from ``gups_single`` is exactly
# that daemon-issued-extent entry/exit and handle-visible residency, not
# the update kernel.


@partial(jax.jit, donate_argnums=0, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _gups_handle_run(arena, steps: int, batch: int, words: int, seed: int,
                     off: int, gdev: int, method: str, mesh):
    def shard_fn(shard):  # shard: (1, row_bytes) — this device's arena row
        me = jax.lax.axis_index(NODE_AXIS)
        row = shard[0]

        # Slice + bitcast the extent ONCE around the update loop, not per
        # step (the measurement shape documented in the module comment):
        # the uint8→uint32 bitcast is a cross-lane byte relayout that cost
        # ~40% of the measured rate when paid every iteration (r5 first
        # light: handle 0.051 vs single 0.087 GUPS), and hoisting it is
        # observationally identical — the donated arena row only becomes
        # visible when the jit returns, with or without per-step
        # write-back.
        raw = jax.lax.dynamic_slice(row, (off,), (4 * words,))
        tbl0 = jax.lax.bitcast_convert_type(raw.reshape(words, 4), jnp.uint32)

        def body(i, tbl):
            key = jax.random.fold_in(jax.random.key(seed), i)
            idx = jax.random.randint(key, (batch,), 0, words, dtype=jnp.int32)
            if method == "bincount":
                return tbl + jnp.bincount(idx, length=words).astype(jnp.uint32)
            return tbl.at[idx].add(jnp.uint32(1))

        tbl = jax.lax.fori_loop(0, steps, body, tbl0)
        back = jax.lax.bitcast_convert_type(tbl, jnp.uint8).reshape(-1)
        updated = jax.lax.dynamic_update_slice(row, back, (off,))
        # Only the handle's device mutates its row: on a multi-device plane
        # every other row (and any allocation living there) is untouched,
        # and `updates = steps * batch` counts exactly what landed.
        return jnp.where(me == gdev, updated, row)[None]

    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(NODE_AXIS, None),
        out_specs=P(NODE_AXIS, None),
    )(arena)


def gups_handles(
    words: int = 1 << 20,
    batch: int = 1 << 14,
    steps: int = 32,
    seed: int = 0,
    method: str = "scatter",
    plane=None,
) -> dict:
    """GUPS over an ocm handle allocated END TO END through the control
    plane: an in-process daemon cluster places the table as a device-kind
    allocation (``ctx.alloc``), the plane serves the bytes, and the timed
    program enters the daemon-issued extent once, applies the update
    rounds, and exits back through it (only the handle's device row is
    mutated — see the module comment for the exact measurement shape).
    Reset and conservation read-back go through ``ctx.put``/``ctx.get_as``
    — the full public path. Pass a dedicated bench ``plane`` (or none — a
    fresh loopback plane is made), not one holding live allocations."""
    from oncilla_tpu.core.kinds import OcmKind
    from oncilla_tpu.ops.ici import SpmdIciPlane
    from oncilla_tpu.runtime.cluster import local_cluster
    from oncilla_tpu.utils.config import OcmConfig

    nbytes = 4 * words
    if plane is None:
        from oncilla_tpu.parallel.mesh import node_mesh

        mesh = node_mesh(jax.devices()[:1])
        plane = SpmdIciPlane(
            config=OcmConfig(device_arena_bytes=nbytes + (1 << 20)),
            mesh=mesh, devices_per_rank=1,
        )
    mesh = plane.mesh
    cfg = OcmConfig(
        host_arena_bytes=1 << 20,
        device_arena_bytes=plane.config.device_arena_bytes,
    )
    with local_cluster(1, config=cfg) as cl:
        ctx = cl.context(0, ici_plane=plane)
        # A pad first so the table extent sits at a non-zero offset:
        # proves offset addressing, not row 0. (On a 1-node cluster the
        # REMOTE_DEVICE request demotes to LOCAL_DEVICE, alloc.c:82-83 —
        # still daemon-registered, still plane-resident.)
        pad = ctx.alloc(4096, OcmKind.REMOTE_DEVICE)
        handle = ctx.alloc(nbytes, OcmKind.REMOTE_DEVICE)
        off = handle.extent.offset
        assert off != 0, "pad should push the table off offset 0"
        from oncilla_tpu.ops.ici import resolve_global_device

        gdev = resolve_global_device(
            handle, plane.devices_per_rank, int(mesh.devices.size)
        )

        def run(arena):
            return _gups_handle_run(arena, steps, batch, words, seed, off,
                                    gdev, method, mesh)

        plane.update(run)           # warm-up compiles the timed executable
        ctx.put(handle, np.zeros(nbytes, np.uint8))  # reset via the handle
        _fence(plane.arena[0, :8])
        t0 = time.perf_counter()
        plane.update(run)
        _fence(plane.arena[0, :8])
        dt = time.perf_counter() - t0
        updates = steps * batch
        # Conservation, read back THROUGH the handle via the public API.
        tbl = np.asarray(ctx.get_as(handle, (words,), np.uint32))
        total = int(tbl.astype(np.uint64).sum())
        ctx.free(handle)
        ctx.free(pad)
    return {
        "mode": f"handle:{method}",
        "gups": updates / dt / 1e9,
        "updates": updates,
        "seconds": dt,
        "table_sum": total,  # == updates (duplicates accumulate)
    }


def gups_handle_best(
    words: int = 1 << 20,
    batch: int = 1 << 14,
    steps: int = 32,
    seed: int = 0,
) -> dict:
    """Both lowerings over the same handle-backed table; conservation
    gates publishability, best wins."""
    from oncilla_tpu.ops.ici import SpmdIciPlane
    from oncilla_tpu.parallel.mesh import node_mesh
    from oncilla_tpu.utils.config import OcmConfig

    mesh = node_mesh(jax.devices()[:1])
    plane = SpmdIciPlane(
        config=OcmConfig(device_arena_bytes=4 * words + (1 << 20)),
        mesh=mesh, devices_per_rank=1,
    )
    best = None
    for method in ("scatter", "bincount"):
        r = gups_handles(words=words, batch=batch, steps=steps, seed=seed,
                         method=method, plane=plane)
        if r["table_sum"] != r["updates"]:
            continue  # wrong results are not publishable
        if best is None or r["gups"] > best["gups"]:
            best = r
    if best is None:
        raise RuntimeError("no handle-GUPS method produced conserved updates")
    return best


@partial(jax.jit, donate_argnums=0, static_argnums=(1, 2, 3, 4, 5))
def _gups_mesh_run(table, steps: int, per_dest: int, words: int, seed: int, mesh):
    def shard_fn(shard):  # shard: (1, words) — this device's table row
        me = jax.lax.axis_index(NODE_AXIS)
        d = jax.lax.axis_size(NODE_AXIS)

        def body(i, row):
            key = jax.random.fold_in(jax.random.key(seed), me * 1_000_003 + i)
            # Row j of idx targets device j; all_to_all delivers to it.
            idx = jax.random.randint(
                key, (d, per_dest), 0, words, dtype=jnp.int32
            )
            recv = jax.lax.all_to_all(idx, NODE_AXIS, 0, 0)
            return row.at[recv.reshape(-1)].add(jnp.uint32(1))

        return jax.lax.fori_loop(0, steps, body, shard[0])[None]

    return jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P(NODE_AXIS, None),
        out_specs=P(NODE_AXIS, None),
    )(table)


def gups_mesh(
    mesh=None,
    words_per_dev: int = 1 << 18,
    batch: int = 1 << 12,
    steps: int = 32,
    seed: int = 0,
) -> dict:
    """Cross-chip GUPS: each device issues ``batch`` random updates per step,
    each targeting a uniformly random word on a uniformly random device; the
    index batches ride ICI via all_to_all. The table is laid out exactly like
    the SPMD arena (one row per chip's HBM, ``arena_sharding``)."""
    mesh = mesh if mesh is not None else node_mesh()
    d = mesh.devices.size
    per_dest = max(1, batch // d)
    def fresh():
        return jax.device_put(
            jnp.zeros((d, words_per_dev), dtype=jnp.uint32), arena_sharding(mesh)
        )

    _fence(_gups_mesh_run(fresh(), steps, per_dest, words_per_dev, seed, mesh))
    table = fresh()
    _fence(table)
    t0 = time.perf_counter()
    table = _gups_mesh_run(table, steps, per_dest, words_per_dev, seed, mesh)
    _fence(table)
    dt = time.perf_counter() - t0
    updates = steps * d * d * per_dest  # per step: d sources x d dests x per_dest
    total = int(np.asarray(table).astype(np.uint64).sum())
    return {
        "mode": f"mesh:{d}dev",
        "gups": updates / dt / 1e9,
        "updates": updates,
        "seconds": dt,
        "table_sum": total,  # == updates (duplicates accumulate)
    }


def main() -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=["single", "mesh"], default="single")
    ap.add_argument("--words", type=int, default=1 << 20)
    ap.add_argument("--batch", type=int, default=1 << 14)
    ap.add_argument("--steps", type=int, default=64)
    args = ap.parse_args()

    if args.mode == "mesh":
        out = gups_mesh(
            words_per_dev=args.words, batch=args.batch, steps=args.steps
        )
    else:
        out = gups_single(words=args.words, batch=args.batch, steps=args.steps)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
