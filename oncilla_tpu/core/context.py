"""The application-side context: alloc / free / put / get / copy.

Analogue of libocm (/root/reference/src/lib.c + inc/oncillamem.h): the façade
the app links against. ``ocm_init`` returns an :class:`Ocm`; handles are
:class:`OcmAlloc`; ``ocm_copy`` composes the kind×kind matrix the reference
implements as a 9-way switch (/root/reference/src/lib.c:502-665).

Local arms (LOCAL_HOST, LOCAL_DEVICE) are served in-process from this host's
arenas — the reference's single-node shortcut where ``alloc_find`` forces host
memory when the cluster has one node (/root/reference/src/alloc.c:82-83).
Remote arms require a control plane (a :class:`RemoteBackend`, wired in by
:mod:`oncilla_tpu.runtime`); without one they raise ``OcmConnectError``.
"""

from __future__ import annotations

import itertools
import threading
from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.analysis import alloctrace
from oncilla_tpu.core.arena import Extent, check_bounds
from oncilla_tpu.core.errors import (
    OcmConnectError,
    OcmInvalidHandle,
)
from oncilla_tpu.core.handle import OcmAlloc
from oncilla_tpu.core.hbm import DeviceArena, from_bytes
from oncilla_tpu.core.hostmem import HostArena
from oncilla_tpu.core.kinds import Fabric, OcmKind
from oncilla_tpu.utils.config import OcmConfig
from oncilla_tpu.utils.debug import GLOBAL_TRACER, printd


class RemoteBackend(Protocol):
    """What the runtime plugs in to serve remote arms. One-sided semantics:
    after ``alloc`` returns, ``put``/``get`` involve no remote application
    code (the reference's data plane bypasses the daemon per-transfer,
    SURVEY.md §1 "two disjoint planes")."""

    def alloc(self, nbytes: int, kind: OcmKind) -> OcmAlloc: ...
    def free(self, handle: OcmAlloc) -> None: ...
    def put(self, handle: OcmAlloc, data, offset: int) -> None: ...
    def get(self, handle: OcmAlloc, nbytes: int, offset: int): ...


class Ocm:
    """Per-process oncilla context (``ocm_init``/``ocm_tini`` pair,
    /root/reference/src/lib.c:98,160)."""

    def __init__(
        self,
        config: OcmConfig | None = None,
        remote: RemoteBackend | None = None,
        devices=None,
    ):
        self.config = config or OcmConfig()
        self._remote = remote
        self.host_arena = HostArena(
            self.config.host_arena_bytes, self.config.alignment
        )
        if devices is None:
            devices = jax.local_devices()[:1]
        self.device_arenas = [
            DeviceArena(self.config.device_arena_bytes, d, self.config.alignment)
            for d in devices
        ]
        # Local alloc ids: odd counter so they never collide with the
        # daemon's even pod-wide ids (rem_alloc_id analogue, mem.c:45).
        self._next_id = itertools.count(1, 2)
        self._allocs: dict[int, OcmAlloc] = {}  # the lib.c:84 allocs list
        # Lazy app-side staging buffers for remote handles (the lib.c:255
        # malloc'd local arm); released on free.
        self._stagebufs: dict[int, np.ndarray] = {}
        # True only when ocm_init created the backend for this context
        # (tini then closes it); injected backends stay the caller's.
        self._owns_remote = False
        self._lock = threading.Lock()
        self.tracer = GLOBAL_TRACER
        # Scope key for the OCM_ALLOCTRACE=1 allocation ledger (id-based:
        # contexts sharing a backend must not share a ledger scope).
        self._trace_scope = f"ctx:{id(self):#x}"

    # -- lifecycle -------------------------------------------------------

    def __enter__(self) -> "Ocm":
        return self

    def __exit__(self, *exc) -> None:
        self.tini()

    def tini(self) -> None:
        """Free every live handle and detach from the daemon (``ocm_tini``,
        lib.c:160; also covers the reference's missing app-death
        reclamation, main.c:6-7)."""
        if alloctrace.enabled():
            # Still-live handles here were leaked by the app (tini is the
            # reclaim-of-last-resort): report each with its allocation
            # site before the frees below erase the evidence.
            report = alloctrace.note_tini(self._trace_scope)
            if report["count"]:
                printd(
                    "tini: %d leaked alloc(s) totalling %d B reclaimed",
                    report["count"], report["bytes"],
                )
                for entry in report["live"]:
                    printd(
                        "tini leak: alloc %d (%d B, %s) from %s [%s]",
                        entry["alloc_id"], entry["nbytes"], entry["kind"],
                        entry["site"], entry["thread"],
                    )
        with self._lock:
            handles = list(self._allocs.values())
        for h in handles:
            try:
                self.free(h)
            except OcmInvalidHandle:
                pass
        # Only close a backend this context created for itself (ocm_init's
        # nodefile auto-attach): an injected client may be shared by other
        # contexts at the same (pid, rank) identity, and closing it would
        # DISCONNECT-reclaim their live allocations too.
        if self._owns_remote:
            close = getattr(self._remote, "close", None)
            if close is not None:
                close()

    # -- alloc / free ----------------------------------------------------

    def _local_arena(self, kind: OcmKind, device_index: int):
        if kind == OcmKind.LOCAL_HOST:
            return self.host_arena
        if not 0 <= device_index < len(self.device_arenas):
            raise OcmInvalidHandle(
                f"device_index {device_index} out of range "
                f"(host has {len(self.device_arenas)} arena(s))"
            )
        return self.device_arenas[device_index]

    def _remote_or_raise(self, kind) -> RemoteBackend:
        if self._remote is None:
            raise OcmConnectError(
                f"kind {kind} needs a control plane; ocm_init was "
                "called without one (single-node mode)"
            )
        return self._remote

    def alloc(
        self,
        nbytes: int,
        kind: OcmKind = OcmKind.LOCAL_HOST,
        device_index: int = 0,
        local_nbytes: int | None = None,
        deadline_ms: int | None = None,
    ) -> OcmAlloc:
        """``ocm_alloc`` (/root/reference/src/lib.c:175). ``local_nbytes``
        (remote kinds only) sizes the app-side staging window smaller than
        the remote region — the reference's asymmetric
        ``local_alloc_bytes`` idiom (/root/reference/test/ocm_test.c:35-47,
        mismatch handshake test ib_client.c:194-242); one-sided push/pull
        then move window-sized pieces at explicit remote offsets."""
        if local_nbytes is not None:
            if kind in (OcmKind.LOCAL_HOST, OcmKind.LOCAL_DEVICE):
                raise OcmInvalidHandle(
                    "local_nbytes applies to remote kinds (local arms have "
                    "no staging window)"
                )
            if not 0 < local_nbytes <= nbytes:
                raise OcmInvalidHandle(
                    f"local_nbytes {local_nbytes} must be in (0, {nbytes}]"
                )
        with self.tracer.span("alloc"):
            if kind in (OcmKind.LOCAL_HOST, OcmKind.LOCAL_DEVICE):
                di = 0 if kind == OcmKind.LOCAL_HOST else device_index
                ext = self._local_arena(kind, di).alloc(nbytes)
                h = OcmAlloc(
                    alloc_id=next(self._next_id),
                    kind=kind,
                    fabric=Fabric.LOCAL,
                    nbytes=nbytes,
                    rank=0,
                    device_index=di,
                    extent=ext,
                    origin_rank=0,
                )
            else:
                kw = ({} if deadline_ms is None
                      else {"deadline_ms": deadline_ms})
                h = self._remote_or_raise(kind).alloc(nbytes, kind, **kw)
                h.local_nbytes = local_nbytes
            with self._lock:
                self._allocs[h.alloc_id] = h
            alloctrace.note_alloc(
                self._trace_scope, h.alloc_id, nbytes, h.kind.name
            )
            printd("alloc id=%d kind=%s nbytes=%d", h.alloc_id, kind, nbytes)
            return h

    def free(self, handle: OcmAlloc) -> None:
        """``ocm_free`` (/root/reference/src/lib.c:347) — with the NULL-check
        ordering bug (lib.c:357-359) not replicated."""
        self.free_many([handle])

    def free_many(self, handles) -> int:
        """``ocm_free`` for several handles at once. The checks of
        :meth:`free` are made for every handle first (a double free, or a
        handle listed twice, raises before anything is released), the
        books are brought up in one pass under the lock, and the
        LOCAL_DEVICE extents of one arena are scrubbed and released
        together (``DeviceArena.free_many``: a dispatch a group where the
        arena has been told their size, and every extent scrubbed before
        any is released); every other kind is released as :meth:`free`
        always has. Returns the device programs the scrubs took."""
        handles = list(handles)
        with self._lock:
            ids = set()
            for h in handles:
                if h is None:
                    raise OcmInvalidHandle("free(None)")
                if (h.freed or h.alloc_id not in self._allocs
                        or h.alloc_id in ids):
                    raise OcmInvalidHandle(
                        f"double free of alloc {h.alloc_id}")
                ids.add(h.alloc_id)
            for h in handles:
                del self._allocs[h.alloc_id]
                self._stagebufs.pop(h.alloc_id, None)
        by_arena: dict[int, list[OcmAlloc]] = {}
        for h in handles:
            if h.kind == OcmKind.LOCAL_DEVICE and not h.daemon_owned:
                by_arena.setdefault(h.device_index, []).append(h)
                continue
            if h.kind == OcmKind.LOCAL_HOST and not h.daemon_owned:
                self.host_arena.free(h.extent)
            else:
                # A remote kind, or daemon-owned: that includes single-node
                # DEMOTED handles (kind LOCAL_*), whose extent the daemon
                # registered and so must release.
                self._remote_or_raise(h.kind).free(h)
            self._note_freed(h)
        dispatches = 0
        for index, held in by_arena.items():
            dispatches += self.device_arenas[index].free_many(
                [h.extent for h in held])
            for h in held:
                self._note_freed(h)
        return dispatches

    def _note_freed(self, handle: OcmAlloc) -> None:
        handle.freed = True
        alloctrace.note_free(self._trace_scope, handle.alloc_id)

    # -- one-sided ops ---------------------------------------------------

    def _check_live(self, handle: OcmAlloc) -> None:
        if handle.freed:
            raise OcmInvalidHandle(f"use of freed alloc {handle.alloc_id}")

    def put(self, handle: OcmAlloc, data, offset: int = 0,
            deadline_ms: int | None = None) -> None:
        """One-sided write (``ocm_copy_onesided`` op_flag=1,
        /root/reference/src/lib.c:670). ``deadline_ms`` bounds the op's
        total time (resilience/timebudget.py): retry/failover ladders
        clamp to it and an exhausted budget surfaces as typed
        :class:`OcmDeadlineExceeded`. Local arms are a memcpy and
        ignore it."""
        self._check_live(handle)
        data = _coerce_bytes(data)
        raw_n = _nbytes_of(data)
        # Pass the deadline only when set: fake/minimal RemoteBackend
        # implementations (tests, adapters) keep their old signature.
        kw = {} if deadline_ms is None else {"deadline_ms": deadline_ms}
        with self.tracer.span("put", nbytes=raw_n):
            if handle.daemon_owned:
                self._remote_or_raise(handle.kind).put(
                    handle, data, offset, **kw
                )
            elif handle.kind == OcmKind.LOCAL_HOST:
                self.host_arena.write(handle.extent, _to_numpy(data), offset)
            elif handle.kind == OcmKind.LOCAL_DEVICE:
                self.device_arenas[handle.device_index].write(
                    handle.extent, data, offset
                )
            else:
                self._remote_or_raise(handle.kind).put(
                    handle, data, offset, **kw
                )

    def put_many(self, handles, rows) -> int:
        """:meth:`put` of row ``i`` of ``rows`` (a uint8 ``(n, nbytes)``
        array) at the start of ``handles[i]``, ``n`` handles at most. The
        LOCAL_DEVICE handles of one arena are written together
        (``DeviceArena.write_many``) under ONE ``put`` span, whose
        ``nbytes`` is the group's; every other handle as :meth:`put`
        writes it. Returns the device programs the group writes took."""
        handles = list(handles)
        for h in handles:
            self._check_live(h)
        nbytes = int(rows.shape[1])
        by_arena: dict[int, list[int]] = {}
        for i, h in enumerate(handles):
            if h.kind == OcmKind.LOCAL_DEVICE and not h.daemon_owned:
                by_arena.setdefault(h.device_index, []).append(i)
            else:
                self.put(h, rows[i])
        dispatches = 0
        for index, picked in by_arena.items():
            group = (rows if len(picked) == len(handles)
                     else rows[np.asarray(picked)])
            with self.tracer.span("put", nbytes=nbytes * len(picked)):
                dispatches += self.device_arenas[index].write_many(
                    [handles[i].extent for i in picked], group)
        return dispatches

    def get(self, handle: OcmAlloc, nbytes: int | None = None, offset: int = 0,
            out=None, deadline_ms: int | None = None):
        """One-sided read (``ocm_copy_onesided`` op_flag=0). Returns uint8
        bytes: numpy for host arms, jax.Array for device arms.

        ``out`` (a writable C-contiguous uint8 array) selects the
        registered-receive-buffer idiom: the bytes land in the caller's
        buffer (sized by ``out``; via zero-copy ``recv_into`` on the DCN
        path, a fallback copy elsewhere) and ``out`` is returned — a
        fresh destination array per get costs a page fault per 4 KiB,
        which at GB scale is most of the transfer time.

        ``deadline_ms`` bounds the op's total time (see :meth:`put`);
        reads on a replicated handle under an armed ``OCM_HEDGE_MS``
        may additionally be hedged against the replica chain."""
        self._check_live(handle)
        if out is not None:
            nbytes = out.nbytes
        elif nbytes is None:
            nbytes = handle.nbytes - offset
        kw = {} if deadline_ms is None else {"deadline_ms": deadline_ms}
        with self.tracer.span("get", nbytes=nbytes):
            if out is not None:
                backend = (
                    self._remote_or_raise(handle.kind)
                    if (handle.daemon_owned or handle.kind.is_remote)
                    else None
                )
                get_into = getattr(backend, "get_into", None)
                if get_into is not None and handle.kind in (
                    OcmKind.REMOTE_HOST, OcmKind.LOCAL_HOST
                ):
                    return get_into(handle, out, offset, **kw)
                res = (
                    backend.get(handle, nbytes, offset, **kw)
                    if backend is not None
                    else self.get(handle, nbytes, offset)
                )
                flat = out.reshape(-1)
                flat[:] = np.asarray(res).view(np.uint8).reshape(-1)
                return out
            if handle.daemon_owned:
                return self._remote_or_raise(handle.kind).get(
                    handle, nbytes, offset, **kw
                )
            if handle.kind == OcmKind.LOCAL_HOST:
                return self.host_arena.read(handle.extent, nbytes, offset)
            if handle.kind == OcmKind.LOCAL_DEVICE:
                return self.device_arenas[handle.device_index].read(
                    handle.extent, nbytes, offset
                )
            return self._remote_or_raise(handle.kind).get(
                handle, nbytes, offset, **kw
            )

    def get_as(self, handle: OcmAlloc, shape, dtype, offset: int = 0):
        """Typed one-sided read."""
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = self.get(handle, nbytes, offset)
        if isinstance(raw, np.ndarray):
            return raw.view(dtype).reshape(shape)
        return from_bytes(raw, shape, dtype)

    def localbuf(self, handle: OcmAlloc, nbytes: int | None = None):
        """``ocm_localbuf`` (/root/reference/src/lib.c:425-460): the app-side
        window onto an allocation. Zero-copy numpy view for LOCAL_HOST;
        materialized jax.Array for LOCAL_DEVICE. For remote kinds the
        reference mallocs a staging buffer into the handle at alloc time
        (lib.c:255-269) and one-sided ops move between it and the remote
        memory; here the equivalent host staging array is created lazily on
        first request, cached per handle, and released by ``free``. Mutate
        it in place, then ``push``/``pull`` (or ``ocm_copy_onesided`` with
        ``local=None``) to move it over the fabric.

        ``nbytes`` sizes the window smaller than the remote region (the
        ``alloc(local_nbytes=...)`` idiom, settable here instead as long
        as the window has not been created yet); asymmetric windows slide
        over the region via push/pull offsets."""
        self._check_live(handle)
        if nbytes is not None:
            if not (handle.is_remote or handle.daemon_owned):
                raise OcmInvalidHandle(
                    "a sized staging window applies to remote kinds only"
                )
            if not 0 < nbytes <= handle.nbytes:
                raise OcmInvalidHandle(
                    f"window {nbytes} must be in (0, {handle.nbytes}]"
                )
            with self._lock:
                existing = self._stagebufs.get(handle.alloc_id)
                if existing is not None and existing.nbytes != nbytes:
                    raise OcmInvalidHandle(
                        f"staging window already created at "
                        f"{existing.nbytes} B; cannot resize to {nbytes}"
                    )
                handle.local_nbytes = nbytes
        if handle.kind == OcmKind.LOCAL_HOST and not handle.daemon_owned:
            return self.host_arena.view(handle.extent)
        if handle.kind == OcmKind.LOCAL_DEVICE and not handle.daemon_owned:
            return self.device_arenas[handle.device_index].read(
                handle.extent, handle.nbytes
            )
        # Remote kinds AND daemon-owned demoted ones: the bytes live behind
        # the control plane, so the app-side arm is a staging buffer.
        with self._lock:
            # Re-check liveness under the lock: a free() racing in between
            # _check_live and here would otherwise let us cache a buffer for
            # a dead id that nothing ever removes (ids are never reused).
            if handle.alloc_id not in self._allocs:
                raise OcmInvalidHandle(
                    f"alloc {handle.alloc_id} freed during localbuf"
                )
            buf = self._stagebufs.get(handle.alloc_id)
            if buf is None:
                window = handle.local_nbytes or handle.nbytes
                buf = np.zeros(window, dtype=np.uint8)
                self._stagebufs[handle.alloc_id] = buf
        return buf

    def _staging_range(self, handle: OcmAlloc, nbytes: int | None,
                       offset: int, local_offset: int | None) -> tuple:
        """Resolve (n, local_offset) for a push/pull: bounds-checked
        against BOTH the staging window and the remote region. With a
        full-size window and no explicit local_offset, the window mirrors
        the region (local_offset = offset, the original symmetric
        semantics); a smaller window defaults to local_offset 0 — its
        whole content moves to/from the remote ``offset``."""
        if not (handle.is_remote or handle.daemon_owned):
            raise OcmInvalidHandle("push/pull is for remote-kind handles")
        window = handle.local_nbytes or handle.nbytes
        if local_offset is None:
            local_offset = offset if window == handle.nbytes else 0
        if nbytes is None:
            n = min(window - local_offset, handle.nbytes - offset)
        else:
            n = nbytes
        check_bounds(Extent(0, window), local_offset, n)
        check_bounds(Extent(0, handle.nbytes), offset, n)
        return n, local_offset

    def push(self, handle: OcmAlloc, nbytes: int | None = None,
             offset: int = 0, local_offset: int | None = None) -> None:
        """One-sided write of the staging buffer into a remote allocation
        (the ocm_copy_onesided op_flag=1 leg over the handle's own local
        buffer, lib.c:670-700). ``offset`` addresses the remote region;
        ``local_offset`` the staging window (see ``_staging_range`` for
        the defaults)."""
        n, lo = self._staging_range(handle, nbytes, offset, local_offset)
        buf = self.localbuf(handle)
        self.put(handle, np.asarray(buf)[lo:lo + n], offset)

    def pull(self, handle: OcmAlloc, nbytes: int | None = None,
             offset: int = 0, local_offset: int | None = None) -> None:
        """One-sided read of a remote allocation into the staging buffer."""
        n, lo = self._staging_range(handle, nbytes, offset, local_offset)
        buf = self.localbuf(handle)
        buf[lo:lo + n] = np.asarray(self.get(handle, n, offset))

    # -- two-sided copy matrix ------------------------------------------

    def copy(
        self,
        dst: OcmAlloc,
        src: OcmAlloc,
        nbytes: int | None = None,
        dst_offset: int = 0,
        src_offset: int = 0,
    ) -> None:
        """``ocm_copy`` (/root/reference/src/lib.c:502-665): the kind×kind
        matrix. The reference dispatches 9 cases by hand; here every pair
        composes get→put, with same-arena fast paths."""
        self._check_live(dst)
        self._check_live(src)
        if nbytes is None:
            nbytes = min(src.nbytes - src_offset, dst.nbytes - dst_offset)
        with self.tracer.span("copy", nbytes=nbytes):
            if (
                src.kind == OcmKind.LOCAL_DEVICE
                and dst.kind == OcmKind.LOCAL_DEVICE
                and src.device_index == dst.device_index
                and not (src.daemon_owned or dst.daemon_owned)
            ):
                # Fused on-chip move: one jitted slice+update, no host hop.
                self.device_arenas[src.device_index].move(
                    src.extent, dst.extent, nbytes, src_offset, dst_offset
                )
                return
            if (
                src.kind == OcmKind.REMOTE_DEVICE
                and dst.kind == OcmKind.REMOTE_DEVICE
                and self._remote is not None
            ):
                # Device-to-device rides the ICI fabric directly (one-sided
                # chip-to-chip on SpmdIciPlane — the ocm_copy RDMA×RDMA arm
                # going straight to ib_write, lib.c:670-700), never the host.
                plane = getattr(self._remote, "ici_plane", None)
                if plane is not None:
                    plane.copy(dst, src, nbytes, dst_offset, src_offset)
                    return
            data = self.get(src, nbytes, src_offset)
            self.put(dst, data, dst_offset)

    # -- introspection (oncillamem.h parity) ----------------------------

    def status(self, rank: int | None = None) -> dict:
        """Live daemon status (rank, nnodes, live_allocs, bytes live,
        lease/heartbeat health under ``leases``) — the STATUS endpoint.
        On the rank-0 master ``nnodes`` is the JOINED count; poll it
        before depending on remote placement (a still-joining cluster
        demotes remote requests, alloc.c:82-83)."""
        backend = self._remote_or_raise("status")
        return backend.status(rank)

    def fetch_prom(self, rank: int | None = None) -> str:
        """A rank's Prometheus text exposition (STATUS_PROM), fetched
        over the ordinary in-band control path."""
        return self._remote_or_raise("fetch_prom").fetch_prom(rank)

    def start_slo(self, interval_s: float | None = None):
        """Arm the in-process SLO watcher (obs/slo.py) over this
        context's control plane: background STATUS_PROM scrapes feed the
        metrics history, the burn-rate engine evaluates the ``OCM_SLO``
        objectives, and verdicts surface in ``status()["slo"]``.
        Returns the runner, or None when ``OCM_SLO`` disables it."""
        return self._remote_or_raise("start_slo").start_slo(interval_s)

    def stop_slo(self) -> None:
        backend = self._remote
        if backend is not None:
            backend.stop_slo()

    def export_trace(self, path: str, cluster: bool = True) -> dict:
        """Write a Perfetto/Chrome-trace JSON merging this process's
        event journal (``OCM_EVENTS=1``) with — when ``cluster`` and a
        control plane is attached — every reachable daemon's journal
        (STATUS_EVENTS), trace_ids stitched as flows across pid tracks.
        Returns the exporter summary ({events, spans, tracks, flows})."""
        from oncilla_tpu.obs import export, journal

        streams = [journal.events()]
        backend = self._remote
        fetch = getattr(backend, "fetch_events", None)
        if cluster and fetch is not None:
            nnodes = len(getattr(backend, "entries", []) or [])
            for rank in range(nnodes):
                try:
                    streams.append(fetch(rank))
                except Exception as e:  # noqa: BLE001 — merge survivors;
                    # a down daemon must not void the local journal
                    printd("export_trace: rank %d journal unavailable: %s",
                           rank, e)
        return export.write_chrome_trace(export.merge(*streams), path)

    @staticmethod
    def is_remote(handle: OcmAlloc) -> bool:
        """``ocm_is_remote`` — correct version of lib.c:461 (see SURVEY.md
        known-bugs list)."""
        return handle.is_remote

    @staticmethod
    def alloc_kind(handle: OcmAlloc) -> OcmKind:
        return handle.kind

    @staticmethod
    def remote_sz(handle: OcmAlloc) -> int:
        return handle.remote_sz


def _to_numpy(data) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return data
    return np.asarray(data)


def _coerce_bytes(data):
    """Accept raw bytes-likes on the put path (the C surface is void*-based,
    inc/oncillamem.h; a Python caller reasonably hands in bytes)."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        return np.frombuffer(data, dtype=np.uint8)
    return data


def _nbytes_of(data) -> int:
    data = _coerce_bytes(data)
    if isinstance(data, np.ndarray):
        return data.nbytes
    a = jnp.asarray(data)
    return a.size * a.dtype.itemsize


# ---------------------------------------------------------------------------
# Module-level functional API, name-for-name with inc/oncillamem.h:69-89.
# ---------------------------------------------------------------------------

def ocm_init(
    config: OcmConfig | None = None,
    remote: RemoteBackend | None = None,
    devices=None,
    ici_plane=None,
) -> Ocm:
    """``ocm_init`` (/root/reference/src/lib.c:98-132): when the config
    names a nodefile (or ``OCM_NODEFILE`` is set) and no remote backend is
    given, attach to the local daemon automatically — the reference's
    mailbox CONNECT handshake, here the loopback-TCP control plane. Rank
    comes from ``config.rank`` or hostname/``jax.process_index`` detection
    (nodefile.c:92-103). ``ici_plane`` (e.g. ``ops.ici.SpmdIciPlane``)
    enables the REMOTE_DEVICE arm."""
    config = config or OcmConfig()
    owns_remote = False
    if remote is None and config.nodefile:
        from oncilla_tpu.runtime.client import ControlPlaneClient
        from oncilla_tpu.runtime.membership import detect_rank, parse_nodefile

        entries = parse_nodefile(config.nodefile)
        rank = config.rank if config.rank is not None else detect_rank(entries)
        if not 0 <= rank < len(entries):
            raise OcmConnectError(
                f"rank {rank} out of range for the {len(entries)}-node nodefile"
            )
        remote = ControlPlaneClient(
            entries, rank, config=config, ici_plane=ici_plane
        )
        owns_remote = True
    ctx = Ocm(config=config, remote=remote, devices=devices)
    ctx._owns_remote = owns_remote
    return ctx


def ocm_tini(ctx: Ocm) -> None:
    ctx.tini()


def ocm_alloc(ctx: Ocm, nbytes: int, kind: OcmKind = OcmKind.LOCAL_HOST, **kw):
    return ctx.alloc(nbytes, kind, **kw)


def ocm_free(ctx: Ocm, handle: OcmAlloc) -> None:
    ctx.free(handle)


def ocm_localbuf(ctx: Ocm, handle: OcmAlloc, nbytes: int | None = None):
    return ctx.localbuf(handle, nbytes)


def ocm_is_remote(handle: OcmAlloc) -> bool:
    return handle.is_remote


def ocm_alloc_kind(handle: OcmAlloc) -> OcmKind:
    return handle.kind


def ocm_remote_sz(handle: OcmAlloc) -> int:
    return handle.remote_sz


def ocm_copy(ctx: Ocm, dst: OcmAlloc, src: OcmAlloc, **kw) -> None:
    ctx.copy(dst, src, **kw)


def ocm_copy_onesided(
    ctx: Ocm, handle: OcmAlloc, local=None, op: str = "write", offset: int = 0
):
    """``ocm_copy_onesided`` (/root/reference/src/lib.c:670): op is "write"
    (push ``local`` into the allocation) or "read" (return bytes). With
    ``local=None`` on a remote handle, the op moves the handle's own
    staging buffer (``ctx.localbuf``) — the reference's semantics, where
    one-sided ops always use the handle's malloc'd local arm."""
    if op == "write":
        if local is None and (handle.is_remote or handle.daemon_owned):
            ctx.push(handle, offset=offset)
        else:
            ctx.put(handle, local, offset)
        return None
    if op == "read":
        if local is None and (handle.is_remote or handle.daemon_owned):
            ctx.pull(handle, offset=offset)
            # Same shape as the plain-get path: element 0 is the byte at
            # ``offset`` (a view into the staging buffer). With an
            # asymmetric (smaller) window the pull landed at window
            # position 0, so the whole window is that view.
            buf = ctx.localbuf(handle)
            return buf[offset:] if buf.nbytes == handle.nbytes else buf
        n = _nbytes_of(local) if local is not None else None
        return ctx.get(handle, n, offset)
    raise ValueError(f"op must be 'read' or 'write', got {op!r}")


def ocm_copy_out(ctx: Ocm, src: OcmAlloc, nbytes: int | None = None,
                 offset: int = 0):
    """``ocm_copy_out`` (/root/reference/inc/oncillamem.h:84): drain an
    allocation into a fresh local buffer. The reference left this as a −1
    stub (lib.c:491-494); here it is a working one-sided read."""
    return ctx.get(src, nbytes, offset)


def ocm_copy_in(ctx: Ocm, dst: OcmAlloc, src, offset: int = 0) -> None:
    """``ocm_copy_in`` (/root/reference/inc/oncillamem.h:85): fill an
    allocation from a local buffer. The reference left this as a −1 stub
    (lib.c:496-499); here it is a working one-sided write."""
    ctx.put(dst, src, offset)
