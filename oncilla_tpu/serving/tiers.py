"""Tiered KV page store: HBM -> local host arena -> remote arenas.

The storage half of the serving scenario (ROADMAP item 1): fixed-size KV
pages live in exactly one of three tiers —

- ``HOT``  — device HBM extents (``core/hbm.py``'s DeviceArena through an
  :class:`~oncilla_tpu.core.context.Ocm` LOCAL_DEVICE handle): the chip's
  HBM on an accelerator, a jax CPU buffer under the CPU test backend,
  byte-faithful either way. If the device arena cannot take a page the
  store places that allocation one tier down instead of failing, and
  counts the refusal (``stats.degraded``) so a HOT tier that silently
  never holds a page is visible.
- ``WARM`` — this host's DRAM arena (``core/hostmem.py``, LOCAL_HOST).
- ``COLD`` — remote arenas over the existing striped/fabric/mux data
  plane (REMOTE_HOST through a ``ControlPlaneClient`` — or, when the
  store runs without a control plane, a LOCAL_HOST stand-in flagged
  ``cold_sim`` so a benchmark can never mistake loopback for DCN).
- ``FROZEN`` — disk, via an attached :class:`~oncilla_tpu.persist.
  FrozenStore` (``frozen_backend``). The fourth rung (ROADMAP item 5):
  watermark demotion spills COLD victims to CRC-trailed extent files
  instead of destroying them, and a persisted prefix cache restores
  from the same store on warm boot. No backend attached (the default)
  = the tier has zero capacity and every code path is byte-identical
  to the three-tier store.

Movement is **watermark-driven**: each bounded tier demotes LRU pages to
the next tier down when occupancy crosses its high watermark, down to
its low watermark — the same high/low discipline as the daemon reaper's
``_pressure_evict``. Promotion reads through the PR-3 registered-
receive-buffer path (``get(out=)`` / ``get_into``): the store keeps one
page-sized staging buffer and every fetch lands in it, never in a fresh
allocation.

The QoS mapping (PR 6): tiers correspond to priority classes —
``TIER_PRIORITY`` maps HOT/WARM/COLD onto PRIO_HIGH/PRIO_NORMAL/
PRIO_LOW. A deployment gives the cold-tier client a PRIO_LOW profile at
CONNECT, so when a remote owner runs hot the daemon-side evictor and
this store agree on who goes first: cold serving pages are the
preferred victims everywhere. Within the store the serving-side evictor
enforces the matching invariant — a **shared** extent (prefix-cache
page with live references) is never victimized while referenced, just
as ``_pressure_evict`` never takes an active above-low entry.
"""

from __future__ import annotations

import enum
import itertools
import threading
from dataclasses import dataclass, field

import jax
import numpy as np

from oncilla_tpu.core.errors import (
    OcmError,
    OcmInvalidHandle,
    OcmOutOfMemory,
)
from oncilla_tpu.core.handle import OcmAlloc
from oncilla_tpu.core.kinds import OcmKind
from oncilla_tpu.obs import journal as obs_journal
from oncilla_tpu.qos.policy import PRIO_HIGH, PRIO_LOW, PRIO_NORMAL
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.utils.debug import printd


class Tier(enum.Enum):
    HOT = "hbm"
    WARM = "host"
    COLD = "remote"
    FROZEN = "frozen"


#: The PR-6 QoS mapping: what priority class each tier's allocations
#: should declare at CONNECT, so daemon-side pressure eviction and the
#: serving-side evictor enforce one policy. FROZEN shares PRIO_LOW with
#: COLD: both are the preferred victims; FROZEN is just the rung where
#: "victim" stops meaning "destroyed".
TIER_PRIORITY = {
    Tier.HOT: PRIO_HIGH,
    Tier.WARM: PRIO_NORMAL,
    Tier.COLD: PRIO_LOW,
    Tier.FROZEN: PRIO_LOW,
}

_ORDER = (Tier.HOT, Tier.WARM, Tier.COLD, Tier.FROZEN)
#: The tiers' names as ``occupancy()`` and the stats key them, by position
#: in ``_ORDER``. The store's books (``_count`` / ``_bytes`` / ``_cap`` /
#: ``_below``) are lists by that position too: ``_ORDER.index`` compares
#: by identity, where a dict keyed on the enum runs its Python ``__hash__``.
_NAMES = tuple(t.value for t in _ORDER)


@dataclass(frozen=True)
class FrozenPageHandle:
    """Handle for a FROZEN-resident page: the store key of its extent
    file (no arena offset exists — disk is addressed by name)."""

    key: str


@dataclass
class Page:
    """One KV page: ``nbytes`` bytes, the store's ``page_bytes`` at most,
    living in exactly one tier."""

    page_id: int
    nbytes: int
    tier: Tier
    handle: OcmAlloc
    last_use: int = 0
    pins: int = 0
    #: Prefix-cache references (cross-tenant sharing). A page with
    #: ``shared`` set and ``refs > 0`` is immutable and unevictable.
    shared: bool = False
    refs: int = 0
    #: Bumped on every rewrite; stale prefetched bytes are discarded on
    #: version mismatch.
    version: int = 0
    freed: bool = field(default=False, compare=False)


def _page_bytes(data) -> tuple:
    """A page's bytes as a flat uint8 vector, and how many: a ``jax.Array``
    that is one already stays where it lies, on the device."""
    if not (isinstance(data, jax.Array) and data.dtype == np.uint8
            and data.ndim == 1):
        data = np.ascontiguousarray(np.asarray(data)).view(
            np.uint8).reshape(-1)
    return data, int(data.size)


class TieredPageStore:
    """Page store over three tiers with watermark demotion. A page takes
    one slot of ``page_bytes`` in its tier's arena whatever its own size
    (so pages of unlike size leave no holes, and a tier's capacity is a
    count of pages); it keeps its own ``nbytes``, and that many are put,
    got and moved. A family whose page comes in kinds
    (``models/kv_paging.py::PageKind``) builds the store for its largest.

    Single-writer discipline: all tier *mutation* (alloc/promote/demote/
    free) happens on the engine thread; prefetch workers only ever fetch
    bytes (:meth:`fetch_bytes` is read-only and thread-safe), and the
    engine installs the result. ``stats`` mutation is internally locked.
    """

    def __init__(
        self,
        ctx,
        page_bytes: int,
        hot_capacity: int = 8,
        warm_capacity: int = 16,
        cold_backend=None,
        high_pct: int = 90,
        low_pct: int = 70,
        stats: ServingStats | None = None,
        frozen_backend=None,
        cold_capacity: int | None = None,
    ):
        self.ctx = ctx
        self.page_bytes = int(page_bytes)
        # COLD is unbounded in the three-tier store (it is the floor);
        # with a frozen backend attached it must be finite or nothing
        # would ever spill to disk. FROZEN with no backend has zero
        # capacity: every pre-persist code path is untouched.
        if cold_capacity is None:
            cold_capacity = (
                (1 << 30) if frozen_backend is None
                else max(2 * int(warm_capacity), 1)
            )
        self.capacity = {Tier.HOT: int(hot_capacity),
                         Tier.WARM: int(warm_capacity),
                         Tier.COLD: int(cold_capacity),
                         Tier.FROZEN: (1 << 30) if frozen_backend is not None
                         else 0}
        self._cap = tuple(self.capacity[t] for t in _ORDER)
        # The tier a victim of each tier goes down to (None: the floor).
        self._below = (Tier.WARM, Tier.COLD,
                       Tier.FROZEN if frozen_backend is not None else None,
                       None)
        # The books: live pages and their bytes a tier, changed where a
        # page enters a tier (_place), leaves one for another (_move) or
        # leaves the store (free_pages), and nowhere else. Every question
        # that is a count reads them; only _victims walks ``pages``.
        self._count = [0] * len(_ORDER)
        self._bytes = [0] * len(_ORDER)
        self.high_pct = high_pct
        self.low_pct = low_pct
        self.cold_backend = cold_backend
        self.frozen_backend = frozen_backend
        #: True when COLD is simulated in the local host arena (no
        #: control plane attached): benchmarks must label the cell.
        self.cold_sim = cold_backend is None
        # Ephemeral frozen page keys continue past any leftover
        # ``page-N`` files from a prior run so a stale extent is never
        # silently overwritten by an unrelated page.
        frz_start = 0
        if frozen_backend is not None:
            for k in frozen_backend.keys():
                if k.startswith("page-"):
                    try:
                        frz_start = max(frz_start, int(k[5:]))
                    except ValueError:
                        pass
        self._frz_ids = itertools.count(frz_start + 1)
        self.stats = stats or ServingStats()
        self.pages: dict[int, Page] = {}
        self._ids = itertools.count(1)
        self._clock = itertools.count(1)
        # The registered receive buffer for tier moves (PR-3 get(out=)):
        # one page-sized staging window reused by every engine-thread
        # fetch. Prefetch workers bring their own (serving/engine.py).
        self._recvbuf = np.empty(self.page_bytes, dtype=np.uint8)
        self._mu = threading.Lock()
        # Every HOT extent is page_bytes long: pages freed together are
        # scrubbed a dispatch a group (free_pages), by programs the arena
        # builds and runs now, while a set-up pays for them.
        self.ctx.device_arenas[0].prepare_scrub(self.page_bytes)

    # -- tier backends ----------------------------------------------------

    def _alloc_in(self, tier: Tier) -> OcmAlloc:
        if tier == Tier.HOT:
            return self.ctx.alloc(self.page_bytes, OcmKind.LOCAL_DEVICE)
        if tier == Tier.WARM:
            return self.ctx.alloc(self.page_bytes, OcmKind.LOCAL_HOST)
        if tier == Tier.FROZEN:
            if self.frozen_backend is None:
                raise OcmError("no frozen backend attached")
            if not self.frozen_backend.has_room(self.page_bytes):
                raise OcmOutOfMemory("frozen store budget exhausted")
            return FrozenPageHandle(f"page-{next(self._frz_ids)}")
        if self.cold_backend is not None:
            return self.cold_backend.alloc(self.page_bytes,
                                           OcmKind.REMOTE_HOST)
        return self.ctx.alloc(self.page_bytes, OcmKind.LOCAL_HOST)

    def _free_handle(self, tier: Tier, handle: OcmAlloc) -> None:
        if tier == Tier.FROZEN:
            self.frozen_backend.delete(handle.key)
        elif tier == Tier.COLD and self.cold_backend is not None:
            self.cold_backend.free(handle)
        else:
            self.ctx.free(handle)

    def _put(self, tier: Tier, handle: OcmAlloc, data) -> None:
        if tier != Tier.HOT:
            # Only HOT takes a page where it lies on the device.
            data = np.asarray(data)
        if tier == Tier.FROZEN:
            self.frozen_backend.write(
                handle.key, np.asarray(data).tobytes(), meta={"kind": "page"}
            )
        elif tier == Tier.COLD and self.cold_backend is not None:
            self.cold_backend.put(handle, data, 0)
            self.stats.note_remote(data.nbytes, inbound=False)
        else:
            self.ctx.put(handle, data, 0)

    def _get(self, tier: Tier, handle: OcmAlloc, nbytes: int,
             out: np.ndarray | None):
        """Read a page's bytes, landing in ``out`` when given (the
        registered-receive path: ``get_into`` on the DCN leg, ``get(out=)``
        through the context)."""
        if tier == Tier.FROZEN:
            # A slow CRC-verified read; OcmFrozenCorrupt propagates
            # typed — a corrupt extent is refused, never served.
            raw = np.frombuffer(
                self.frozen_backend.read_bytes(handle.key), dtype=np.uint8
            )
            if out is not None:
                out[:nbytes] = raw[:nbytes]
                return out[:nbytes]
            return raw[:nbytes].copy()
        if tier == Tier.COLD and self.cold_backend is not None:
            if out is not None:
                get_into = getattr(self.cold_backend, "get_into", None)
                if get_into is not None:
                    res = get_into(handle, out[:nbytes], 0)
                else:
                    res = out
                    out[:nbytes] = np.asarray(
                        self.cold_backend.get(handle, nbytes, 0)
                    ).view(np.uint8).reshape(-1)
            else:
                res = self.cold_backend.get(handle, nbytes, 0)
            self.stats.note_remote(nbytes, inbound=True)
            return np.asarray(res).view(np.uint8).reshape(-1)[:nbytes]
        if out is not None:
            return np.asarray(
                self.ctx.get(handle, out=out[:nbytes])
            ).reshape(-1)
        raw = self.ctx.get(handle, nbytes, 0)
        return np.asarray(raw).view(np.uint8).reshape(-1)[:nbytes]

    # -- occupancy --------------------------------------------------------

    def occupancy(self) -> dict:
        return {name: {"pages": n, "bytes": b}
                for name, n, b in zip(_NAMES, self._count, self._bytes)}

    def _sync_stats(self) -> None:
        self.stats.set_occupancy(dict(zip(_NAMES, self._count)),
                                 dict(zip(_NAMES, self._bytes)))

    # -- page lifecycle ---------------------------------------------------

    def touch(self, page: Page) -> None:
        page.last_use = next(self._clock)

    def _check_live(self, page: Page) -> None:
        if page.freed or page.page_id not in self.pages:
            raise OcmInvalidHandle(f"use of freed page {page.page_id}")

    def alloc_page(self, data, shared: bool = False,
                   prefer: Tier = Tier.HOT) -> Page:
        """Store one page of bytes, preferring ``prefer`` and degrading
        down-tier when the preferred arena is full, then enforce
        watermarks. ``data`` may be a uint8 vector that lies on the device
        (a ``jax.Array``): a page sited in HOT is then written device to
        device, and pulled to the host only for a tier below."""
        data, nbytes = _page_bytes(data)
        if not 0 < nbytes <= self.page_bytes:
            raise ValueError(
                f"page is {nbytes} B, store built for pages of "
                f"{self.page_bytes} B at most"
            )
        return self._place(
            lambda tier, handle: self._put(tier, handle, data), shared,
            prefer, nbytes)

    def alloc_pages(self, rows, count: int | None = None,
                    shared: bool = False) -> list[Page]:
        """Store the first ``count`` rows of ``rows`` (a uint8 ``(n,
        nbytes)`` array, on the device or not; all ``n`` by default) as
        pages, as that many :meth:`alloc_page` calls would: the same page
        ids, ``last_use`` order, tiers, books and stats. Where HOT takes
        every one at or below its high mark and no tier stands above its
        own, no page can be demoted between them: their HOT extents are
        taken, written by ONE ``Ocm.put_many`` and booked, and the
        watermarks and the stats are brought up once. Otherwise, or where
        the device arena refuses an extent, page by page."""
        n, nbytes = (int(d) for d in rows.shape)
        count = n if count is None else int(count)
        if not 0 < nbytes <= self.page_bytes or not 0 <= count <= n:
            raise ValueError(
                f"{count} pages of {nbytes} B from {n} rows, store built "
                f"for pages of {self.page_bytes} B at most"
            )
        handles = self._hot_extents(count) if self._fits_hot(count) else None
        if handles is None:
            return [self.alloc_page(rows[i], shared=shared)
                    for i in range(count)]
        if not handles:
            return []
        dispatches = self.ctx.put_many(handles, rows)
        pages = []
        for handle in handles:
            page = Page(next(self._ids), nbytes, Tier.HOT, handle,
                        shared=shared)
            self.touch(page)
            self.pages[page.page_id] = page
            self.stats.note_place()
            pages.append(page)
        self._count[0] += count
        self._bytes[0] += count * nbytes
        self.stats.note_hot_write(count, dispatches)
        self.enforce_watermarks()
        self._sync_stats()
        return pages

    def _fits_hot(self, count: int) -> bool:
        """Whether ``count`` more pages in HOT leave it within its
        capacity and high mark, and every tier is within its own: then
        neither a placement's make-room nor a watermark sweep demotes."""
        if self._count[0] + count > self._cap[0]:
            return False
        for i, nxt in enumerate(self._below):
            if nxt is None:
                break
            high = max(self._cap[i] * self.high_pct // 100, 1)
            if self._count[i] + (count if i == 0 else 0) > high:
                return False
        return True

    def _hot_extents(self, count: int) -> list | None:
        """``count`` HOT extents, or None (and none kept) where the device
        arena refuses one."""
        handles = []
        try:
            for _ in range(count):
                handles.append(self._alloc_in(Tier.HOT))
        except OcmError:
            self.ctx.free_many(handles)
            return None
        return handles

    def _place(self, fill, shared: bool, prefer: Tier, nbytes: int) -> Page:
        """Site a new page of ``nbytes`` by the tier policy;
        ``fill(tier, handle)`` writes its bytes into the extent the policy
        chose."""
        last_err: Exception | None = None
        for i in range(_ORDER.index(prefer), len(_ORDER)):
            tier = _ORDER[i]
            # LRU residents demote to make room for the newcomer; if
            # nothing is demotable (all pinned / referenced-shared) the
            # newcomer degrades a tier instead — never the residents.
            self._make_room(tier)
            if self._count[i] >= self._cap[i]:
                continue
            try:
                handle = self._alloc_in(tier)
            except OcmError as e:  # arena full / remote BUSY: degrade a tier
                last_err = e
                self.stats.note_degrade(capacity_free=True)
                printd("serving: %s tier alloc degraded: %s", tier.value, e)
                continue
            fill(tier, handle)
            page = Page(next(self._ids), nbytes, tier, handle,
                        shared=shared)
            self.touch(page)
            self.pages[page.page_id] = page
            self._count[i] += 1
            self._bytes[i] += nbytes
            self.stats.note_place()
            self.enforce_watermarks()
            self._sync_stats()
            return page
        raise OcmError(
            f"no tier can take a page (last error: {last_err})"
        )

    def read_page(self, page: Page, out: np.ndarray | None = None
                  ) -> np.ndarray:
        """The page's bytes (registered-receive into ``out`` when given;
        else into the store's staging buffer for non-hot tiers)."""
        self._check_live(page)
        self.touch(page)
        if out is None and page.tier != Tier.HOT:
            out = self._recvbuf
        return self._get(page.tier, page.handle, page.nbytes, out)

    def write_page(self, page: Page, data) -> None:
        """Rewrite a page in place. Forbidden on a referenced shared
        page — that is what :meth:`cow` is for (a write would corrupt
        every other tenant's context)."""
        self._check_live(page)
        if page.shared and page.refs > 0:
            raise OcmInvalidHandle(
                f"write to shared page {page.page_id} with {page.refs} "
                "live reference(s); copy-on-write first"
            )
        raw, nbytes = _page_bytes(data)
        if nbytes != page.nbytes:
            raise ValueError(f"page write of {nbytes} B into "
                             f"{page.nbytes} B page")
        self._put(page.tier, page.handle, raw)
        page.version += 1
        self.touch(page)

    def cow(self, page: Page) -> Page:
        """Copy-on-write: a private copy of a (typically shared) page,
        placed by the normal tier policy. The original — and every other
        tenant's view of it — is untouched."""
        self._check_live(page)
        def fill(tier: Tier, handle: OcmAlloc) -> None:
            if tier == Tier.HOT and page.tier == Tier.HOT:
                # HBM to HBM on the chip: no host round trip.
                self.ctx.copy(handle, page.handle)
                self.touch(page)
            else:
                self._put(tier, handle,
                          np.array(self.read_page(page), copy=True))

        # Pinned: the clone's own make-room must not demote its source
        # between the tier choice and the copy.
        self.pin(page)
        try:
            clone = self._place(fill, shared=False, prefer=Tier.HOT,
                                nbytes=page.nbytes)
        finally:
            self.unpin(page)
        self.stats.note_cow()
        obs_journal.record("page_cow", src=page.page_id,
                           dst=clone.page_id, nbytes=page.nbytes)
        return clone

    def free_page(self, page: Page) -> None:
        self.free_pages([page])

    def free_pages(self, pages) -> None:
        """Free pages together (one already freed, or listed twice, is
        passed over). Every page is validated first: a shared page with
        live references raises and nothing is freed. The HOT handles go
        back in one ``Ocm.free_many``, their extents scrubbed a dispatch a
        group, every one before any is released; the other tiers' as
        :meth:`_free_handle` frees them; the store's books are brought up
        once."""
        pages = list({p.page_id: p for p in pages if not p.freed}.values())
        for page in pages:
            if page.shared and page.refs > 0:
                raise OcmInvalidHandle(
                    f"free of shared page {page.page_id} with {page.refs} "
                    "live reference(s)"
                )
        if not pages:
            return
        for page in pages:
            del self.pages[page.page_id]
            page.freed = True
            i = _ORDER.index(page.tier)
            self._count[i] -= 1
            self._bytes[i] -= page.nbytes
        dispatches = self.ctx.free_many(
            [p.handle for p in pages if p.tier == Tier.HOT])
        for page in pages:
            if page.tier != Tier.HOT:
                self._free_handle(page.tier, page.handle)
        self.stats.note_frees(len(pages), dispatches)
        self._sync_stats()

    def close(self) -> None:
        """Free every live page (shared ones included: teardown)."""
        pages = list(self.pages.values())
        for page in pages:
            page.refs = 0
        self.free_pages(pages)

    # -- movement ---------------------------------------------------------

    def _move(self, page: Page, to: Tier,
              data: np.ndarray | None = None) -> None:
        """Relocate a page's bytes between tiers. ``data`` short-cuts
        the read when the caller already fetched the current version
        (prefetch); it must be version-checked by the caller."""
        if page.tier == to:
            return
        if data is None:
            data = self.read_page(page)
        src, dst = _ORDER.index(page.tier), _ORDER.index(to)
        try:
            new_handle = self._alloc_in(to)
        except OcmError as e:
            # A full target arena cancels the move, never the page.
            self.stats.note_degrade(
                capacity_free=self._count[dst] < self._cap[dst]
            )
            printd("serving: move of page %d to %s declined: %s",
                   page.page_id, to.value, e)
            return
        self._put(to, new_handle, np.asarray(data))
        with self._mu:
            old_tier, old_handle = page.tier, page.handle
            page.tier, page.handle = to, new_handle
            self._count[src] -= 1
            self._bytes[src] -= page.nbytes
            self._count[dst] += 1
            self._bytes[dst] += page.nbytes
            # Any relocation invalidates in-flight prefetched bytes: a
            # worker mid-read of the OLD extent (freed and scrubbed
            # below) must see its version check fail at install time.
            page.version += 1
        self._free_handle(old_tier, old_handle)
        promote = dst < src
        self.stats.note_move(promote, old_tier.value, to.value)
        obs_journal.record(
            "page_promote" if promote else "page_demote",
            page_id=page.page_id, src=old_tier.value, dst=to.value,
            nbytes=page.nbytes, shared=page.shared, refs=page.refs,
        )
        self._sync_stats()

    def promote(self, page: Page, to: Tier = Tier.HOT,
                data: np.ndarray | None = None,
                version: int | None = None) -> None:
        """Move a page up-tier (the page-fault / prefetch-install path).
        ``data``+``version`` come from a prefetch worker; a version
        mismatch (the page was rewritten since the fetch was issued)
        discards the stale bytes and re-reads."""
        self._check_live(page)
        if version is not None and version != page.version:
            data = None
        if _ORDER.index(to) >= _ORDER.index(page.tier):
            return
        # Make room FIRST so the promotion itself cannot bounce off a
        # full target tier.
        self._make_room(to)
        self._move(page, to, data=data)
        self.touch(page)
        self.enforce_watermarks()

    def promote_many(self, items, to: Tier = Tier.HOT) -> None:
        """Batched promotion for one fused decode tick: ``items`` is an
        iterable of ``(page, data, version)`` (data/version as in
        :meth:`promote`, None for a plain fault). Room is made and pages
        move one at a time (the single-writer discipline is unchanged),
        but the watermark sweep runs ONCE at the end instead of once per
        page — a B-session batch build does O(1) sweeps, not O(B)."""
        moved = False
        for page, data, version in items:
            self._check_live(page)
            if version is not None and version != page.version:
                data = None
            if _ORDER.index(to) >= _ORDER.index(page.tier):
                continue
            self._make_room(to)
            self._move(page, to, data=data)
            self.touch(page)
            moved = True
        if moved:
            self.enforce_watermarks()

    def demote(self, page: Page, to: Tier) -> None:
        self._check_live(page)
        if _ORDER.index(to) <= _ORDER.index(page.tier):
            return
        self._move(page, to)

    def pin(self, page: Page) -> None:
        page.pins += 1

    def unpin(self, page: Page) -> None:
        page.pins = max(0, page.pins - 1)

    # -- watermark eviction ----------------------------------------------

    def _victims(self, tier: Tier) -> list[Page]:
        """Demotion candidates, LRU-first. NEVER a pinned page, and
        NEVER a shared extent while referenced — the serving-side twin
        of the reaper's never-an-active-above-low guarantee. The one walk
        over every live page the store makes (counted: ``places.walks``),
        so callers ask only once a page really has to go down."""
        self.stats.note_walk()
        return sorted(
            (p for p in self.pages.values()
             if p.tier == tier and p.pins == 0
             and not (p.shared and p.refs > 0)),
            key=lambda p: p.last_use,
        )

    def _make_room(self, tier: Tier) -> None:
        """Demote until ``tier`` has a free slot (promotion headroom)."""
        i = _ORDER.index(tier)
        nxt = self._below[i]
        if nxt is None:
            return
        while self._count[i] >= self._cap[i]:
            victims = self._victims(tier)
            if not victims:
                return  # everything pinned/referenced: overshoot allowed
            self._make_room(nxt)
            self._move(victims[0], nxt)

    def enforce_watermarks(self) -> None:
        """High/low watermark demotion per bounded tier, exactly the
        daemon reaper's ``_pressure_evict`` shape: past high, demote
        LRU victims down to low. With a frozen backend attached, COLD is
        bounded too and spills to disk — the demote-to-FROZEN leg."""
        for i, nxt in enumerate(self._below):
            if nxt is None:
                break
            cap = self._cap[i]
            # Floor at one page: integer watermark math on a tiny tier
            # must never read "demote everything, always".
            high = max(cap * self.high_pct // 100, 1)
            low = max(cap * self.low_pct // 100, 1)
            if self._count[i] <= high:
                continue
            for victim in self._victims(_ORDER[i]):
                if self._count[i] <= low:
                    break
                self._move(victim, nxt)

    # -- prefetch support -------------------------------------------------

    def fetch_bytes(self, page: Page, out: np.ndarray) -> tuple[int, bool]:
        """Thread-safe read of a page's bytes into the caller's
        registered buffer (prefetch workers): returns (version, ok).
        Read-only — tier installation happens on the engine thread via
        :meth:`promote`."""
        with self._mu:
            if page.freed:
                return (page.version, False)
            tier, handle, version = page.tier, page.handle, page.version
        try:
            self._get(tier, handle, page.nbytes, out)
        except OcmError:
            return (version, False)
        return (version, True)
