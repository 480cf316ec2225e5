#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the ~1.1B bf16 flagship
(``benchmarks/mfu.py::chip_filling_config``, full depth, seeded weights):

1. **device** — refuses to start unless ``jax.default_backend() == "tpu"``;
2. **memory_plane** — ``ocm_init`` + alloc/put/get/copy/free on
   ``LOCAL_DEVICE`` byte-exact at an unaligned sub-MiB size (XLA slice path)
   and at 1 MiB and 64 MiB aligned (the Pallas DMA row kernels), plus the
   typed-error probes;
3. **weights** — the flagship's seeded weights, on the first chip only;
4. **multichip** (only with >= 2 devices, after the weights so the chips'
   allocators have different histories) — one arena per chip on its own
   device, and put/get/copy between every ordered pair of chips byte-exact
   with guard blocks, over per-chip ``DeviceArena``s and over the SPMD
   arena's two transports (CollectivePermute and the compiled Pallas
   remote-DMA kernel), bounded by a timeout that fails loudly;
5. **serving** — ``ServingEngine`` + ``TieredPageStore`` + ``PrefixCache``
   over an in-process ``local_cluster`` COLD tier: more requests than
   ``max_batch`` sharing a system prefix, HOT/WARM smaller than the working
   set so pages cross HOT -> WARM -> COLD and back;
6. **reference** — the plain unpaged ``llama.forward`` teacher-forced on
   prompt + generated tokens on the same device: max |dlogit| and the share
   of generated tokens that are the reference's arg-max, against the
   thresholds in :class:`Sizing`;
7. **kernels** — the Pallas kernels the legs relied on were built with
   ``interpret=False`` and were dispatched.

Standard output is two JSON lines: the report (what every phase saw, its
wall seconds and compile counts), then the verdict the driver reads,
``{"ok": ..., "device": {"platform", "kind", "count"}}`` and nothing else.
Any failed phase stops the run: both lines then carry ``"ok": false``, the
report names the phase, and the exit code is 1. Wall seconds per phase are
wall time with compilation included, not a benchmark.

``--cpu-rehearsal`` runs the same legs at tiny size on the CPU backend (what
``tests/`` calls, and the dry run before spending chip time); its line says
``"platform": "cpu"``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import threading
import time
import traceback

# The driver's limit is 1200 s, compilation included; stop short of it so
# the failure line is this script's, not a kill.
RUN_DEADLINE_S = 1100.0
# The remote-DMA kernel is the one leg that can hang a chip.
MULTICHIP_DEADLINE_S = 300.0
# The largest host the chip tool offers; more devices only repeat pairs.
MAX_MESH = 4


def _flagship_model():
    from oncilla_tpu.benchmarks.mfu import chip_filling_config

    return chip_filling_config()[0]


def _tiny_model():
    from oncilla_tpu.models import LlamaConfig

    return LlamaConfig.tiny()


@dataclasses.dataclass(frozen=True)
class Sizing:
    """One sizing of the smoke. ``FLAGSHIP`` is what the chip runs;
    ``REHEARSAL`` keeps the same legs inside tier-1's budget on a CPU."""

    model: object                   # () -> LlamaConfig
    mem_arena: int                  # memory-plane device arena
    mem_sizes: tuple[int, ...]      # first one unaligned (XLA slice path)
    mesh_arena: int                 # per-chip arena of the multichip leg
    mesh_nbytes: int                # one chip-to-chip transfer
    page_tokens: int
    shared_tokens: int              # the system prefix every request opens with
    suffix_tokens: tuple[int, ...]  # per request; request 1 repeats request 0
    new_tokens: int
    hot: int
    warm: int
    max_batch: int
    # Reference agreement. bf16: the paged step and the plain forward round
    # every activation to 8 mantissa bits and tile their matmuls differently,
    # so logits of O(1) drift by a few 1e-2 over 16 layers and the arg-max
    # of near-flat random-weight logits flips on near-ties: measured on a
    # v5e (PR 21) max |dlogit| 0.058 on logits up to 5.2, arg-max share
    # 0.972 (140 of 144), the same in seven runs; the flagship's limits
    # are about twice that drift. float32 on the CPU agrees to 1e-6, and
    # tests/test_serving.py shows the rehearsal failing here when one page
    # comes back from COLD with its words permuted.
    logit_tol: float
    argmax_floor: float


FLAGSHIP = Sizing(
    model=_flagship_model,
    mem_arena=256 << 20,
    mem_sizes=(300_001, 1 << 20, 64 << 20),
    mesh_arena=8 << 20,
    mesh_nbytes=1 << 20,
    page_tokens=16,             # 2 MiB float32 pages at the flagship width
    shared_tokens=192,
    suffix_tokens=(16, 16, 24, 8, 16, 40),
    new_tokens=24,
    hot=16,
    warm=3,
    max_batch=4,
    logit_tol=0.12,
    argmax_floor=0.9,
)

REHEARSAL = Sizing(
    model=_tiny_model,
    mem_arena=8 << 20,
    mem_sizes=(30_001, 64 << 10, 1 << 20),
    mesh_arena=256 << 10,
    mesh_nbytes=32 << 10,
    page_tokens=8,
    shared_tokens=24,
    suffix_tokens=(8, 8, 12, 4, 8, 20),
    new_tokens=8,
    hot=6,
    warm=3,
    max_batch=4,
    logit_tol=1e-3,
    argmax_floor=1.0,
)


def check(cond: bool, msg: str) -> None:
    """Unlike ``assert`` this survives ``python -O``."""
    if not cond:
        raise AssertionError(msg)


def emit(report: dict) -> None:
    """The report, then as the LAST stdout line the verdict: exactly
    ``ok`` and ``device``, which is all the driver's check accepts there."""
    print(json.dumps(report))
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)


@contextlib.contextmanager
def deadline(seconds: float, label: str, line: dict):
    """Fail loudly if the block outlives ``seconds``: a kernel hung on the
    chip blocks inside a C call no exception can reach, so a thread prints
    the failure lines and hard-exits."""
    done = threading.Event()

    def watch() -> None:
        if done.wait(seconds):
            return
        line.update(ok=False, failed=label,
                    error=f"no result after {seconds:.0f} s (hung?)")
        emit(line)
        os._exit(1)

    threading.Thread(target=watch, daemon=True, name=f"deadline-{label}").start()
    try:
        yield
    finally:
        done.set()


# -- 1. device ---------------------------------------------------------------


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


class CompileMeter:
    """Per phase: executables built, the seconds the backend spent
    compiling them or fetching them from the persistent cache, the cache
    hits, and the seconds JAX spent tracing and lowering (host Python,
    paid for every new shape whether or not the cache is warm) — read off
    JAX's own monitoring events."""

    _TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                     "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax.monitoring

        self.totals = {"executables": 0, "seconds": 0.0, "cache_hits": 0,
                       "trace_lower_seconds": 0.0}
        self._mark = dict(self.totals)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.totals["executables"] += 1
            self.totals["seconds"] += duration_secs
        elif event in self._TRACE_EVENTS:
            self.totals["trace_lower_seconds"] += duration_secs

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1

    def lap(self) -> dict:
        then, self._mark = self._mark, dict(self.totals)
        return {k: round(v - then[k], 1) for k, v in self.totals.items()}


# -- 7. kernel evidence ------------------------------------------------------


class KernelLedger:
    """Which Pallas DMA kernels a leg built and dispatched. The factories in
    ``ops/pallas_ici.py`` are ``lru_cache``d on ``(rows, arena shape,
    interpret, ...)``: a leg's expected keys with ``interpret=False`` must
    already be cached (probing them is a hit, never a miss), they must be
    the ONLY keys the leg added (so nothing was built interpreted), each
    one's jit must hold a compiled executable, and every
    ``pallas_*`` call is one cache lookup, which counts the dispatches."""

    def __init__(self):
        from oncilla_tpu.ops import pallas_ici as pi

        self.factories = {
            "write_rows": pi._cached_rows_write,
            "read_rows": pi._cached_rows_read,
            "local_copy": pi._cached_local_copy,
            "ici_copy": pi._cached_ici_copy,
        }
        self.mark()

    def mark(self) -> None:
        self._before = {k: f.cache_info() for k, f in self.factories.items()}

    def settle(self, expected: dict[str, list[tuple]]) -> dict:
        """``expected[family]`` lists the factory argument tuples the leg
        must have built (each with interpret=False). Returns dispatches per
        family; raises if the cache tells another story."""
        out = {}
        for fam, factory in self.factories.items():
            before, now = self._before[fam], factory.cache_info()
            keys = expected.get(fam, [])
            check(now.currsize - before.currsize == len(keys),
                  f"{fam}: leg built {now.currsize - before.currsize} "
                  f"kernel(s), expected exactly the {len(keys)} compiled "
                  f"one(s) {keys}")
            for key in keys:
                fn = factory(*key)
                check(factory.cache_info().misses == now.misses,
                      f"{fam}{key}: not built with interpret=False")
                check(fn._cache_size() >= 1,
                      f"{fam}{key}: built but never dispatched")
            calls = (now.hits + now.misses) - (before.hits + before.misses)
            check(calls > 0 or not keys, f"{fam}: never called")
            out[fam] = {"dispatches": calls, "compiled": len(keys)}
        self.mark()
        return out


# -- 2. memory plane ---------------------------------------------------------


def memory_plane(sz: Sizing, on_tpu: bool, ledger: KernelLedger,
                 report: dict) -> None:
    import numpy as np

    import oncilla_tpu as ocm
    from oncilla_tpu import OcmKind
    from oncilla_tpu.ops.pallas_ici import BLOCK

    rng = np.random.default_rng(21)
    ctx = ocm.ocm_init(ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=sz.mem_arena,
    ))
    arena = ctx.device_arenas[0]
    check(arena.buffer.devices() == {arena.device},
          "device arena is not on its device")
    for n in sz.mem_sizes:
        a = ctx.alloc(n, OcmKind.LOCAL_DEVICE)
        b = ctx.alloc(n, OcmKind.LOCAL_DEVICE)
        data = rng.integers(0, 256, n, dtype=np.uint8)
        ctx.put(a, data)
        check(np.array_equal(np.asarray(ctx.get(a)), data),
              f"put/get mismatch at {n} B")
        ctx.copy(b, a)
        check(np.array_equal(np.asarray(ctx.get(b)), data),
              f"copy mismatch at {n} B")
        check(np.array_equal(np.asarray(ctx.get(a)), data),
              f"copy clobbered its source at {n} B")
        ctx.free(a)
        ctx.free(b)
        z = ctx.alloc(n, OcmKind.LOCAL_DEVICE)
        check(not np.asarray(ctx.get(z)).any(),
              f"recycled extent not scrubbed at {n} B")
        ctx.free(z)

    # Typed errors (the verify skill's probes).
    h = ctx.alloc(4096, OcmKind.LOCAL_DEVICE)
    for exc, probe in (
        (ocm.OcmBoundsError, lambda: ctx.put(h, np.zeros(8192, np.uint8))),
        (ocm.OcmOutOfMemory,
         lambda: ctx.alloc(2 * sz.mem_arena, OcmKind.LOCAL_DEVICE)),
        (ocm.OcmConnectError, lambda: ctx.alloc(4096, OcmKind.REMOTE_HOST)),
    ):
        _expect(exc, probe)
    ctx.free(h)
    _expect(ocm.OcmInvalidHandle, lambda: ctx.get(h))
    _expect(ocm.OcmInvalidHandle, lambda: ctx.free(h))
    check(arena.allocator.bytes_live == 0, "memory-plane arena not drained")
    ctx.tini()

    report["sizes"] = list(sz.mem_sizes)
    if on_tpu:
        shape = (sz.mem_arena,)
        dma = [n for n in sz.mem_sizes if n % BLOCK == 0 and n >= 1 << 20]
        report["kernels"] = ledger.settle({
            "write_rows": [(n // BLOCK, shape, False) for n in dma],
            "read_rows": [(n // BLOCK, shape, False, 1) for n in dma],
            "local_copy": [(n // BLOCK, shape, False) for n in dma],
        })


def _expect(exc: type, probe) -> None:
    try:
        probe()
    except exc:
        return
    raise AssertionError(f"expected {exc.__name__}")


# -- 4. several chips --------------------------------------------------------


def multichip(sz: Sizing, devices, on_tpu: bool, ledger: KernelLedger,
              report: dict) -> None:
    """One arena per chip, each on its own device; every ordered pair of
    chips copied byte-exact between guard blocks."""
    import jax
    import numpy as np

    import oncilla_tpu as ocm
    from oncilla_tpu import OcmKind
    from oncilla_tpu.ops import pallas_ici as pi
    from oncilla_tpu.ops.ici import (
        IciDataPlane,
        SpmdIciPlane,
        resolve_global_device,
    )
    from oncilla_tpu.ops.pallas_ici import BLOCK
    from oncilla_tpu.parallel.mesh import node_mesh
    from oncilla_tpu.runtime.cluster import local_cluster

    nd, n = len(devices), sz.mesh_nbytes
    rng = np.random.default_rng(33)
    guard = np.full(BLOCK, 0xA5, np.uint8)
    pairs = [(i, j) for i in range(nd) for j in range(nd) if i != j]
    cfg = ocm.OcmConfig(
        host_arena_bytes=4 << 20, device_arena_bytes=sz.mesh_arena,
        heartbeat_s=0.5,
    )

    def guarded_copy(c, src, dst, label: str, copy=None) -> None:
        """dst is n + 2 guard blocks: stamp guards through context ``c``,
        copy src into the middle (``c.copy`` unless ``copy`` is given),
        verify payload and both guards."""
        data = rng.integers(0, 256, n, dtype=np.uint8)
        c.put(src, data, 0)
        c.put(dst, guard, 0)
        c.put(dst, guard, BLOCK + n)
        if copy is None:
            c.copy(dst, src, n, dst_offset=BLOCK)
        else:
            copy(dst, src)
        got = np.asarray(c.get(dst, n + 2 * BLOCK, 0))
        check(np.array_equal(got[BLOCK:BLOCK + n], data),
              f"{label}: payload mismatch")
        check(np.array_equal(got[:BLOCK], guard)
              and np.array_equal(got[BLOCK + n:], guard),
              f"{label}: guard block clobbered")

    # (a) Ocm with one DeviceArena per chip.
    ctx = ocm.Ocm(config=cfg, devices=devices)
    for i, arena in enumerate(ctx.device_arenas):
        check(arena.buffer.devices() == {devices[i]},
              f"Ocm arena {i} lives on {arena.buffer.devices()}")
    for i, j in pairs:
        src = ctx.alloc(n, OcmKind.LOCAL_DEVICE, device_index=i)
        dst = ctx.alloc(n + 2 * BLOCK, OcmKind.LOCAL_DEVICE, device_index=j)
        guarded_copy(ctx, src, dst, f"Ocm.copy chip {i}->{j}")
        ctx.free(src)
        ctx.free(dst)
    ctx.tini()

    def on_every_chip(ctxs, nbytes: int) -> dict:
        """One REMOTE_DEVICE handle per chip (a rank's request is placed on
        another rank, so ask from every rank until all chips hold one)."""
        held: dict[int, tuple] = {}
        spare = []
        for attempt in range(4 * nd):
            c = ctxs[attempt % nd]
            h = c.alloc(nbytes, OcmKind.REMOTE_DEVICE)
            g = resolve_global_device(h, 1, nd)
            if g in held:
                spare.append((c, h))
            else:
                held[g] = (c, h)
            if len(held) == nd:
                break
        for c, h in spare:
            c.free(h)
        check(len(held) == nd, f"placement reached chips {sorted(held)} only")
        return held

    def free_all(held: dict) -> None:
        for c, h in held.values():
            c.free(h)

    report.update(chips=nd, pairs=len(pairs), nbytes=n)

    # (b) IciDataPlane: per-chip arenas, chip-to-chip device_put.
    with local_cluster(nd, config=cfg, ndevices=1) as cl:
        plane = IciDataPlane(config=cfg, devices=devices, devices_per_rank=1)
        for i, arena in enumerate(plane.arenas):
            check(arena.buffer.devices() == {devices[i]},
                  f"IciDataPlane arena {i} lives on {arena.buffer.devices()}")
        ctxs = [cl.context(r, ici_plane=plane) for r in range(nd)]
        srcs = on_every_chip(ctxs, n)
        dsts = on_every_chip(ctxs, n + 2 * BLOCK)
        for i, j in pairs:
            guarded_copy(dsts[j][0], srcs[i][1], dsts[j][1],
                         f"IciDataPlane chip {i}->{j}")
        free_all(srcs)
        free_all(dsts)

    # (c) SpmdIciPlane: one mesh-sharded arena, both ici_copy transports.
    mesh = node_mesh(devices)
    with local_cluster(nd, config=cfg, ndevices=1) as cl:
        plane = SpmdIciPlane(config=cfg, mesh=mesh, devices_per_rank=1)
        for shard in plane.arena.addressable_shards:
            check(shard.data.devices() == {devices[shard.index[0].start]},
                  f"SPMD arena row {shard.index[0].start} lives on "
                  f"{shard.data.devices()}")
        ctxs = [cl.context(r, ici_plane=plane) for r in range(nd)]
        srcs = on_every_chip(ctxs, n)
        dsts = on_every_chip(ctxs, n + 2 * BLOCK)
        ledger.mark()
        # use_pallas=None is what ctx.copy passes: on a TPU it must be the
        # remote-DMA kernel, never a quiet CollectivePermute.
        transports = {"ppermute": False, "pallas": True, "default": None}

        def pallas_lookups() -> int:
            # Every copy the kernel carries is a lookup of its compiled
            # (or, off the chip, windowed interpreted) executable.
            infos = [f.cache_info() for f in
                     (pi._cached_ici_copy, pi._cached_window_copy)]
            return sum(i.hits + i.misses for i in infos)

        for name, use_pallas in transports.items():
            # None goes through ctx.copy; a named transport straight to
            # the plane, which is the only layer that takes the choice.
            copy = None if use_pallas is None else functools.partial(
                plane.copy, nbytes=n, dst_offset=BLOCK,
                use_pallas=use_pallas,
            )
            before = pallas_lookups()
            for i, j in pairs:
                guarded_copy(dsts[j][0], srcs[i][1], dsts[j][1],
                             f"SpmdIciPlane[{name}] chip {i}->{j}", copy)
            carried = pallas_lookups() - before
            want_pallas = on_tpu if use_pallas is None else use_pallas
            check(carried >= len(pairs) if want_pallas else carried == 0,
                  f"SpmdIciPlane[{name}]: the remote-DMA kernel carried "
                  f"{carried} transfer(s), pallas expected: {want_pallas}")
        check(plane.stats["ici_copies"] == 3 * len(pairs),
              f"ici copies {plane.stats['ici_copies']} != {3 * len(pairs)}")
        free_all(srcs)
        free_all(dsts)
        jax.block_until_ready(plane.arena)
    report["transports"] = list(transports)
    if on_tpu:
        report["kernels"] = ledger.settle({"ici_copy": [
            (n // BLOCK, sz.mesh_arena, mesh, False, False)
        ]})
        check(report["kernels"]["ici_copy"]["dispatches"] == 2 * len(pairs),
              "the remote-DMA kernel did not carry both its transports' "
              f"copies: {report['kernels']['ici_copy']}")


# -- 5. serving --------------------------------------------------------------


def serving(sz: Sizing, cfg, params, on_tpu: bool, ledger: KernelLedger,
            report: dict) -> list:
    from oncilla_tpu.ops.pallas_ici import BLOCK
    from oncilla_tpu.runtime.cluster import local_cluster
    from oncilla_tpu.serving.__main__ import (
        _assert_drained,
        _build_engine,
        _cluster_cfg,
        _cold_client,
    )
    from oncilla_tpu.serving.engine import Request, ServingEngine

    prompts = _prompts(sz, cfg.vocab)
    page_bytes = ServingEngine.page_nbytes(cfg, sz.page_tokens)
    # COLD holds whatever HOT and WARM cannot: size each daemon for all of it.
    pages_bound = sum(
        -(-(len(p) + sz.new_tokens) // sz.page_tokens) + 1 for p in prompts
    )
    cluster_cfg = _cluster_cfg(
        host_arena_bytes=max(32 << 20, 2 * pages_bound * page_bytes),
    )
    ledger.mark()
    with local_cluster(3, config=cluster_cfg) as cl:
        cold = _cold_client(cl, 0)
        ctx, store, engine = _build_engine(
            cfg, params, page_tokens=sz.page_tokens, hot=sz.hot,
            warm=sz.warm, cold_client=cold, share=True, name="chip-smoke",
            prefetch_workers=2, max_active=sz.max_batch,
            max_batch=sz.max_batch, keep_logits=True,
        )
        try:
            for r, toks in enumerate(prompts):
                engine.submit(Request(tenant=f"r{r}", tokens=toks,
                                      max_new_tokens=sz.new_tokens))
            t0 = time.perf_counter()
            results = engine.run()
            run_s = time.perf_counter() - t0
            meta = engine.metrics_meta()
            arena_shape = ctx.device_arenas[0].buffer.shape
        finally:
            engine.close()
            store.close()
            hot_left = ctx.device_arenas[0].allocator.bytes_live
            warm_left = ctx.host_arena.allocator.bytes_live
            ctx.tini()
            cold.close()
        check(hot_left == 0 and warm_left == 0,
              f"arenas not drained at close: device {hot_left} B, "
              f"host {warm_left} B")
        drained = _assert_drained(cl)

    hops = meta["moves"]["hops"]
    report.update({
        "requests": len(prompts),
        "max_batch": sz.max_batch,
        "prompt_tokens": [len(p) for p in prompts],
        "new_tokens": sz.new_tokens,
        "page_bytes": page_bytes,
        "tokens": meta["tokens"],
        "prefix_hits": meta["prefix"]["hits"],
        "prefix_tokens_reused": sum(r.prefix_tokens_reused for r in results),
        "cow": meta["prefix"]["cow"],
        "hops": hops,
        "tier_pages_peak": meta["tier_pages_peak"],
        "degraded": meta["degraded"],
        "remote_bytes": meta["remote_bytes"],
        "batch_steps": meta["batch"]["steps"],
        "prefill_chunks": meta["batch"]["prefill_chunks"],
        "stalls": meta["stalls"],
        "drained_ranks": drained,
        "engine_run_wall_s": round(run_s, 2),
    })
    check(len(results) == len(prompts), "a request was lost")
    for res in results:
        check(len(res.out_tokens) == sz.new_tokens,
              f"{res.tenant}: {len(res.out_tokens)} tokens, "
              f"wanted {sz.new_tokens}")
        check(all(0 <= t < cfg.vocab for t in res.out_tokens),
              f"{res.tenant}: token id out of range")
    for hop in ("hbm>host", "host>remote", "remote>hbm", "host>hbm"):
        check(hops.get(hop, 0) > 0, f"no page ever moved {hop}: {hops}")
    check(meta["prefix"]["hits"] > 0, "no prefix hit")
    check(meta["prefix"]["cow"] > 0, "the repeated prompt never took CoW")
    check(meta["tier_pages_peak"].get("hbm", 0) > 0,
          "the HOT tier never held a page")
    check(meta["degraded"]["capacity_free"] == 0,
          f"a tier with free capacity refused a page: {meta['degraded']}")
    check(not meta["cold_sim"], "COLD was simulated, not remote")
    check(meta["remote_bytes"]["in"] > 0 and meta["remote_bytes"]["out"] > 0,
          f"no bytes crossed to the COLD daemons: {meta['remote_bytes']}")
    check(meta["batch"]["size_max"] == sz.max_batch,
          f"fused steps never filled the batch: {meta['batch']['size_max']}")
    check(meta["batch"]["prefill_chunks"] > 0, "no chunked prefill ran")

    if on_tpu:
        rows = page_bytes // BLOCK
        report["kernels"] = ledger.settle({
            "write_rows": [(rows, arena_shape, False)],
            "read_rows": [(rows, arena_shape, False, 1)],
            "local_copy": [(rows, arena_shape, False)],
        })
    return [(p, r) for p, r in zip(prompts, _by_tenant(results))]


def _prompts(sz: Sizing, vocab: int) -> list[list[int]]:
    """One system prefix, then each request diverges by its own suffix
    length; request 1 repeats request 0 (the copy-on-write pair)."""
    import numpy as np

    rng = np.random.default_rng(1234)
    shared = rng.integers(1, vocab, sz.shared_tokens).tolist()
    prompts: list[list[int]] = []
    for r, n in enumerate(sz.suffix_tokens):
        if r == 1:
            prompts.append(list(prompts[0]))
        else:
            prompts.append(shared + rng.integers(1, vocab, n).tolist())
    return prompts


def _by_tenant(results) -> list:
    return sorted(results, key=lambda r: int(r.tenant[1:]))


# -- 6. reference ------------------------------------------------------------


def reference(sz: Sizing, cfg, params, served: list, report: dict) -> None:
    """Teacher-force the plain unpaged forward on prompt + generated tokens
    and compare, row by row, with the logits the engine picked from."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from oncilla_tpu.models import llama

    seqs = [p + r.out_tokens[:-1] for p, r in served]
    width = max(len(s) for s in seqs)
    # Causal attention: right padding cannot reach an earlier position.
    tokens = np.zeros((len(seqs), width), np.int32)
    for b, s in enumerate(seqs):
        tokens[b, :len(s)] = s
    first = np.asarray([len(p) - 1 for p, _ in served])
    rows = first[:, None] + np.arange(sz.new_tokens)[None, :]

    @jax.jit
    def ref_rows(params, tokens, rows):
        logits = llama.forward(params, tokens, cfg)
        return jnp.take_along_axis(logits, rows[:, :, None], axis=1)

    ref = np.asarray(ref_rows(params, jnp.asarray(tokens), jnp.asarray(rows)))
    eng = np.stack([np.stack(r.out_logits) for _, r in served])
    out = np.asarray([r.out_tokens for _, r in served])
    check(ref.shape == eng.shape == (len(served), sz.new_tokens, cfg.vocab),
          f"logit shapes {ref.shape} vs {eng.shape}")
    check(bool(np.isfinite(eng).all() and np.isfinite(ref).all()),
          "non-finite logits")
    check(bool((eng.argmax(-1) == out).all()),
          "a returned token is not the arg-max of its returned logits")
    dmax = float(np.abs(eng - ref).max())
    share = float((ref.argmax(-1) == out).mean())
    # How far below the reference's best the engine's pick sits.
    picked = np.take_along_axis(ref, out[:, :, None], axis=2)[..., 0]
    gap = float((ref.max(-1) - picked).max())
    report.update({
        "max_abs_dlogit": round(dmax, 6),
        "argmax_share": round(share, 4),
        "max_ref_gap_of_pick": round(gap, 6),
        "logit_tol": sz.logit_tol,
        "argmax_floor": sz.argmax_floor,
        "ref_logit_absmax": round(float(np.abs(ref).max()), 3),
        "dtype": cfg.dtype,
    })
    check(dmax <= sz.logit_tol,
          f"max |dlogit| {dmax:.4g} over the {sz.logit_tol} tolerance")
    check(share >= sz.argmax_floor,
          f"arg-max share {share:.3f} under {sz.argmax_floor}")


# -- driver ------------------------------------------------------------------


def run(sz: Sizing, line: dict) -> None:
    import jax

    from oncilla_tpu.models import init_params_host

    on_tpu = line["device"]["platform"] == "tpu"
    phases, walls, compiles = line["phases"], line["wall_s"], line["compile"]
    meter = CompileMeter()

    @contextlib.contextmanager
    def phase(name: str):
        """A leg fills its report as it goes, so a failed check still
        leaves what the leg saw in the line."""
        line["failed"] = name
        report = phases[name] = {}
        t0 = time.perf_counter()
        try:
            yield report
        finally:
            walls[name] = round(time.perf_counter() - t0, 1)
            compiles[name] = meter.lap()
            print(f"chip_smoke: {name} took {walls[name]} s "
                  f"(compile {compiles[name]}): {json.dumps(report)}",
                  file=sys.stderr, flush=True)

    ledger = KernelLedger()
    with phase("memory_plane") as report:
        memory_plane(sz, on_tpu, ledger, report)
    cfg = sz.model()
    with phase("weights") as report:
        params = init_params_host(0, cfg)
        jax.block_until_ready(params)
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
        report.update(
            dim=cfg.dim, layers=cfg.n_layers, heads=cfg.n_heads,
            kv_heads=cfg.n_kv_heads, ffn=cfg.ffn_hidden, vocab=cfg.vocab,
            dtype=cfg.dtype, param_gb=round(nbytes / 1e9, 3),
        )
    devices = jax.local_devices()[:MAX_MESH]
    if len(devices) >= 2:
        # After the weights, as in a deployment: chip 0's allocator has a
        # history the other chips' do not when the first copy is posted.
        with phase("multichip") as report, deadline(
                MULTICHIP_DEADLINE_S, "multichip", line):
            multichip(sz, devices, on_tpu, ledger, report)
    with phase("serving") as report:
        served = serving(sz, cfg, params, on_tpu, ledger, report)
    with phase("reference") as report:
        reference(sz, cfg, params, served, report)
    stats = jax.local_devices()[0].memory_stats() or {}
    line["device_memory"] = {
        k: stats[k] for k in ("peak_bytes_in_use", "bytes_limit")
        if k in stats
    }
    del line["failed"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run the same legs at tiny size on the CPU backend (tests, "
             "and the dry run before spending chip time)",
    )
    args = ap.parse_args(argv)

    import jax

    from oncilla_tpu.utils.platform import (
        describe_devices,
        enable_compile_cache,
    )

    want = "cpu" if args.cpu_rehearsal else "tpu"
    backend = jax.default_backend()
    if backend != want:
        print(f"chip_smoke: backend is {backend!r}, need {want!r}"
              + ("" if args.cpu_rehearsal else
                 " (no CPU path is taken without --cpu-rehearsal)"),
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    line = {
        "ok": False,
        "device": describe_devices(),
        "versions": versions(),
        "sizing": "rehearsal" if args.cpu_rehearsal else "flagship",
        "phases": {},
        "wall_s": {},
        "compile": {},
    }
    print(f"chip_smoke: {json.dumps(line['device'])} "
          f"{json.dumps(line['versions'])} compile cache {cache_dir}",
          file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    try:
        with deadline(RUN_DEADLINE_S, "run", line):
            run(REHEARSAL if args.cpu_rehearsal else FLAGSHIP, line)
        line["ok"] = True
    except Exception as e:  # the one boundary: report the phase, then fail
        traceback.print_exc()
        line["error"] = f"{type(e).__name__}: {e}"
    line["wall_s"]["total"] = round(time.perf_counter() - t0, 1)
    emit(line)
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
