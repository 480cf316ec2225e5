"""The tier store keeps its books by counting: a page count and a byte count
a tier, changed where a page is placed, moved or freed. Held here to a recount
by walking ``store.pages``, and, decision for decision, to a twin store that
still decides by walking (the methods as they stood before the counts, kept
below); the ``places`` counter says how often the store walked at all.
CPU-only: counts, tiers and bytes, never a time."""

from __future__ import annotations

import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.core.errors import OcmError
from oncilla_tpu.core.kinds import OcmKind
from oncilla_tpu.obs import journal as obs_journal
from oncilla_tpu.persist import FrozenStore
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.tiers import _ORDER, Page, Tier, TieredPageStore
from oncilla_tpu.utils.debug import printd

PB = 4 << 10


# -- the twin: the store that decides by walking ------------------------------


class WalkingStore(TieredPageStore):
    """``TieredPageStore`` with every method that asks how full a tier is as
    it stood before the store kept counts: each answers by ``_live``, a walk
    over every page of every tier. It never reads the kept counts."""

    def _live(self, tier: Tier) -> list[Page]:
        return [p for p in self.pages.values() if p.tier == tier]

    def occupancy(self) -> dict:
        out = {}
        for t in _ORDER:
            live = self._live(t)
            out[t.value] = {"pages": len(live),
                            "bytes": sum(p.nbytes for p in live)}
        return out

    def _sync_stats(self) -> None:
        occ = self.occupancy()
        self.stats.set_occupancy(
            {k: v["pages"] for k, v in occ.items()},
            {k: v["bytes"] for k, v in occ.items()},
        )

    def _place(self, fill, shared: bool, prefer: Tier, nbytes: int) -> Page:
        start = _ORDER.index(prefer)
        last_err: Exception | None = None
        for tier in _ORDER[start:]:
            self._make_room(tier)
            if len(self._live(tier)) >= self.capacity[tier]:
                continue
            try:
                handle = self._alloc_in(tier)
            except OcmError as e:
                last_err = e
                self.stats.note_degrade(capacity_free=True)
                continue
            fill(tier, handle)
            page = Page(next(self._ids), nbytes, tier, handle,
                        shared=shared)
            self.touch(page)
            self.pages[page.page_id] = page
            self.enforce_watermarks()
            self._sync_stats()
            return page
        raise OcmError(
            f"no tier can take a page (last error: {last_err})"
        )

    def _move(self, page: Page, to: Tier,
              data: np.ndarray | None = None) -> None:
        if page.tier == to:
            return
        if data is None:
            data = self.read_page(page)
        try:
            new_handle = self._alloc_in(to)
        except OcmError as e:
            self.stats.note_degrade(
                capacity_free=len(self._live(to)) < self.capacity[to]
            )
            printd("twin: move of page %d to %s declined: %s",
                   page.page_id, to.value, e)
            return
        self._put(to, new_handle, np.asarray(data))
        with self._mu:
            old_tier, old_handle = page.tier, page.handle
            page.tier, page.handle = to, new_handle
            page.version += 1
        self._free_handle(old_tier, old_handle)
        promote = _ORDER.index(to) < _ORDER.index(old_tier)
        self.stats.note_move(promote, old_tier.value, to.value)
        obs_journal.record(
            "page_promote" if promote else "page_demote",
            page_id=page.page_id, src=old_tier.value, dst=to.value,
            nbytes=page.nbytes, shared=page.shared, refs=page.refs,
        )
        self._sync_stats()

    def _victims(self, tier: Tier) -> list[Page]:
        return sorted(
            (p for p in self._live(tier)
             if p.pins == 0 and not (p.shared and p.refs > 0)),
            key=lambda p: p.last_use,
        )

    def _make_room(self, tier: Tier) -> None:
        nxt = {Tier.HOT: Tier.WARM, Tier.WARM: Tier.COLD}.get(tier)
        if tier == Tier.COLD and self.frozen_backend is not None:
            nxt = Tier.FROZEN
        if nxt is None:
            return
        while len(self._live(tier)) >= self.capacity[tier]:
            victims = self._victims(tier)
            if not victims:
                return
            self._make_room(nxt)
            self._move(victims[0], nxt)

    def enforce_watermarks(self) -> None:
        pairs = [(Tier.HOT, Tier.WARM), (Tier.WARM, Tier.COLD)]
        if self.frozen_backend is not None:
            pairs.append((Tier.COLD, Tier.FROZEN))
        for tier, nxt in pairs:
            cap = self.capacity[tier]
            high = max(cap * self.high_pct // 100, 1)
            low = max(cap * self.low_pct // 100, 1)
            if len(self._live(tier)) <= high:
                continue
            for victim in self._victims(tier):
                if len(self._live(tier)) <= low:
                    break
                self._move(victim, nxt)


class RecordingCold:
    """A COLD backend of its own (another context's host arena) that writes
    down what the store asked of it."""

    def __init__(self):
        self.ctx = ocm.Ocm(config=ocm.OcmConfig(
            host_arena_bytes=1 << 20, device_arena_bytes=1 << 12))
        self.log: list[tuple] = []

    def alloc(self, nbytes, kind):
        self.log.append(("alloc", nbytes))
        return self.ctx.alloc(nbytes, OcmKind.LOCAL_HOST)

    def free(self, handle):
        self.log.append(("free", handle.nbytes))
        self.ctx.free(handle)

    def put(self, handle, data, offset):
        self.log.append(("put", int(np.asarray(data).nbytes)))
        self.ctx.put(handle, data, offset)

    def get(self, handle, nbytes, offset):
        self.log.append(("get", nbytes))
        return self.ctx.get(handle, nbytes, offset)


class Side:
    """One store of the pair, with what it needs closed after it and a log
    of every move it made (page, from, to), in order."""

    def __init__(self, cls, *, hot, warm, arena_pages, frozen_dir, cold, **kw):
        self.ctx = ocm.Ocm(config=ocm.OcmConfig(
            host_arena_bytes=1 << 20, device_arena_bytes=arena_pages * PB))
        self.cold = RecordingCold() if cold == "backend" else None
        frozen = FrozenStore(str(frozen_dir)) if frozen_dir else None
        self.store = cls(self.ctx, PB, hot_capacity=hot, warm_capacity=warm,
                         cold_backend=self.cold, frozen_backend=frozen,
                         stats=ServingStats("counts"), **kw)
        self.moves: list[tuple] = []
        inner = self.store._move

        def logged(page, to, data=None):
            src = page.tier
            inner(page, to, data=data)
            if page.tier != src:
                self.moves.append((page.page_id, src, page.tier))

        self.store._move = logged

    def close(self):
        self.store.close()
        self.ctx.tini()
        if self.cold is not None:
            self.cold.ctx.tini()


def recount(store) -> dict:
    """Occupancy by walking ``store.pages``, whatever the store keeps."""
    out = {t.value: {"pages": 0, "bytes": 0} for t in _ORDER}
    for page in store.pages.values():
        out[page.tier.value]["pages"] += 1
        out[page.tier.value]["bytes"] += page.nbytes
    return out


def books(side) -> dict:
    snap = side.store.stats.snapshot()
    return {k: snap[k] for k in ("tier_pages", "tier_bytes",
                                 "tier_pages_peak", "moves", "degraded")}


def state(store) -> dict:
    return {pid: (p.tier, p.nbytes, p.pins, p.refs, p.shared, p.version,
                  p.last_use) for pid, p in store.pages.items()}


def held_to_a_recount_and_the_twin(a, b, peak):
    store = a.store
    occ = recount(store)
    assert store.occupancy() == occ
    assert store._count == [occ[t.value]["pages"] for t in _ORDER]
    assert store._bytes == [occ[t.value]["bytes"] for t in _ORDER]
    snap = store.stats.snapshot()
    assert snap["tier_pages"] == {k: v["pages"] for k, v in occ.items()}
    assert snap["tier_bytes"] == {k: v["bytes"] for k, v in occ.items()}
    for k, v in occ.items():
        peak[k] = max(peak.get(k, 0), v["pages"])
    # the peak the stats hold is no lower than any state seen between two
    # operations, and is the twin's (which saw every state in between)
    assert all(snap["tier_pages_peak"].get(k, 0) >= n
               for k, n in peak.items())
    assert state(store) == state(b.store)
    assert b.store.occupancy() == occ
    assert a.moves == b.moves
    assert books(a) == books(b)
    if a.cold is not None:
        assert a.cold.log == b.cold.log


def both(a, b, op):
    """Run ``op(side)`` on both; what one raises the other raises."""
    got = []
    for side in (a, b):
        try:
            got.append(("ok", op(side)))
        except OcmError as e:
            got.append(("raised", type(e).__name__))
    assert got[0][0] == got[1][0], got
    if got[0][0] == "raised":
        assert got[0] == got[1]
        return None
    return got[0][1], got[1][1]


@pytest.mark.parametrize("cold", ["cold_sim", "backend"])
@pytest.mark.parametrize("frozen", [False, True], ids=["three", "frozen"])
@pytest.mark.parametrize("seed, hot, warm, arena_pages", [
    (1, 4, 3, 8),       # the arena holds what HOT's capacity promises
    (2, 5, 2, 4),       # the arena refuses below HOT's capacity: degrades
    (3, 1, 1, 4),       # one page a tier: the watermarks' floor of one
])
def test_a_random_walk_keeps_the_counts_and_decides_as_the_walking_twin(
        tmp_path, cold, frozen, seed, hot, warm, arena_pages):
    kw = dict(hot=hot, warm=warm, arena_pages=arena_pages, cold=cold)
    a = Side(TieredPageStore, frozen_dir=tmp_path / "a" if frozen else None,
             **kw)
    b = Side(WalkingStore, frozen_dir=tmp_path / "b" if frozen else None,
             **kw)
    rng = np.random.default_rng(seed)
    peak: dict = {}
    tiers_seen, ops_run = set(), {}

    def live():
        return sorted(a.store.pages)

    def pick(n=1):
        ids = live()
        return list(rng.choice(ids, size=min(n, len(ids)), replace=False))

    def page_of(side, pid):
        return side.store.pages[int(pid)]

    try:
        for step in range(260):
            kinds = ["alloc", "alloc", "alloc", "free", "promote",
                     "promote_many", "demote", "cow", "pin", "unpin",
                     "ref", "unref", "read"]
            if step % 97 == 96:
                kinds = ["close"]
            kind = kinds[rng.integers(len(kinds))]
            if not live() and kind not in ("alloc", "close"):
                kind = "alloc"
            if len(live()) > 30:
                kind = "free"
            ops_run[kind] = ops_run.get(kind, 0) + 1
            if kind == "alloc":
                nbytes = int(rng.choice([PB, PB, PB // 2, 96]))
                data = rng.integers(1, 256, nbytes, dtype=np.uint8)
                shared = bool(rng.random() < 0.25)
                prefer = _ORDER[int(rng.choice([0, 0, 0, 1, 2]))]
                both(a, b, lambda s: s.store.alloc_page(
                    data, shared=shared, prefer=prefer).page_id)
            elif kind == "free":
                ids = [i for i in pick(int(rng.integers(1, 4)))
                       if not (page_of(a, i).shared and page_of(a, i).refs)]
                both(a, b, lambda s: s.store.free_pages(
                    [page_of(s, i) for i in ids]))
            elif kind in ("promote", "demote"):
                (pid,), to = pick(), _ORDER[int(rng.integers(
                    0, 4 if frozen else 3))]
                both(a, b, lambda s: getattr(s.store, kind)(
                    page_of(s, pid), to))
            elif kind == "promote_many":
                ids, to = pick(3), _ORDER[int(rng.integers(0, 2))]
                both(a, b, lambda s: s.store.promote_many(
                    [(page_of(s, i), None, None) for i in ids], to))
            elif kind == "cow":
                (pid,) = pick()
                both(a, b, lambda s: s.store.cow(page_of(s, pid)).page_id)
            elif kind in ("pin", "unpin"):
                (pid,) = pick()
                both(a, b, lambda s: getattr(s.store, kind)(page_of(s, pid)))
            elif kind in ("ref", "unref"):
                # what the prefix cache does to a shared extent's page
                shared = [i for i in live() if page_of(a, i).shared]
                if shared:
                    pid = shared[int(rng.integers(len(shared)))]
                    for s in (a, b):
                        page = page_of(s, pid)
                        page.refs = (page.refs + 1 if kind == "ref"
                                     else max(page.refs - 1, 0))
            elif kind == "read":
                (pid,) = pick()
                got = both(a, b, lambda s: bytes(
                    s.store.read_page(page_of(s, pid))))
                assert got is None or got[0] == got[1]
            else:
                both(a, b, lambda s: s.store.close())
                assert not a.store.pages
            tiers_seen |= {p.tier for p in a.store.pages.values()}
            held_to_a_recount_and_the_twin(a, b, peak)
        # the walk went where the books matter
        assert tiers_seen >= set(_ORDER[:4 if frozen else 3])
        assert a.moves and all(ops_run.get(k) for k in (
            "alloc", "free", "promote", "promote_many", "demote", "cow",
            "close"))
        assert books(a)["moves"]["demote"] and books(a)["moves"]["promote"]
        if arena_pages < hot:
            assert sum(books(a)["degraded"].values()) > 0
        for pid in live():
            assert bytes(a.store.read_page(page_of(a, pid))) == bytes(
                b.store.read_page(page_of(b, pid)))
    finally:
        a.close()
        b.close()
    assert a.store.occupancy() == recount(a.store) == {
        t.value: {"pages": 0, "bytes": 0} for t in _ORDER}


# -- how often the store walks ------------------------------------------------


class WatchedPages(dict):
    """``store.pages`` that counts every walk over itself."""

    walks = 0

    def _walked(self):
        self.walks += 1

    def values(self):
        self._walked()
        return super().values()

    def items(self):
        self._walked()
        return super().items()

    def keys(self):
        self._walked()
        return super().keys()

    def __iter__(self):
        self._walked()
        return super().__iter__()


def plain_store(hot, warm=4, arena_pages=None, **kw):
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20,
        device_arena_bytes=(arena_pages or hot + 2) * PB))
    store = TieredPageStore(ctx, PB, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("walks"), **kw)
    store.pages = WatchedPages()
    return ctx, store


def places(store) -> dict:
    return store.stats.snapshot()["places"]


@pytest.mark.parametrize("n", [1, 40])
def test_with_hot_ample_pages_placed_moved_and_freed_make_no_walk(n):
    ctx, store = plain_store(hot=2 * n + 8)
    data = np.full(PB, 7, np.uint8)
    try:
        assert places(store) == {"pages": 0, "walks": 0}
        pages = [store.alloc_page(data) for _ in range(n)]
        clone = store.cow(pages[0])
        store.demote(pages[-1], Tier.WARM)
        store.promote(pages[-1])
        store.promote_many([(p, None, None) for p in pages])
        store.enforce_watermarks()
        assert store.occupancy()["hbm"] == {"pages": n + 1,
                                            "bytes": (n + 1) * PB}
        store.free_pages(pages[: n // 2])
        for page in pages[n // 2:]:
            store.free_page(page)
        assert places(store) == {"pages": n + 1, "walks": 0}
        # and nothing else walked the pages behind the counter's back
        assert store.pages.walks == 0
        store.free_page(clone)
        assert not store.pages and store.pages.walks == 0
    finally:
        store.close()
        ctx.tini()


@pytest.mark.parametrize("warm, per_place", [(64, 1), (2, 2)],
                         ids=["warm-ample", "warm-full-too"])
def test_a_tier_at_capacity_makes_a_walk_a_victim_not_a_walk_a_page(
        warm, per_place):
    """Marks at 100 %: a full tier stays full, so every placement finds HOT
    at its capacity and seeks one victim there (and, with WARM full too,
    one more in WARM): a walk each, however many pages are alive."""
    hot = 24
    ctx, store = plain_store(hot=hot, warm=warm, high_pct=100, low_pct=100)
    data = np.full(PB, 9, np.uint8)
    try:
        pages = [store.alloc_page(data) for _ in range(hot)]
        if warm < hot:                 # fill WARM to its capacity as well
            pages += [store.alloc_page(data, prefer=Tier.WARM)
                      for _ in range(warm)]
        assert places(store)["walks"] == 0 == store.pages.walks
        assert store.occupancy()["hbm"]["pages"] == hot
        before = store.stats.snapshot()["moves"]["demote"]
        for k in range(1, 11):
            pages.append(store.alloc_page(data))
            got = places(store)
            assert got["walks"] == per_place * k == store.pages.walks
            assert store.stats.snapshot()["moves"]["demote"] - before == (
                per_place * k)
        assert got["pages"] == len(pages) == len(store.pages)
        # past the high mark one sweep finds every victim in one walk
        store.high_pct, store.low_pct = 50, 25
        walks, demoted = store.pages.walks, store.stats.snapshot()[
            "moves"]["demote"]
        store.enforce_watermarks()
        moved = store.stats.snapshot()["moves"]["demote"] - demoted
        assert moved >= hot - hot // 4
        assert store.pages.walks - walks == places(store)["walks"] - got[
            "walks"] <= 3
    finally:
        store.close()
        ctx.tini()


def test_pinned_and_referenced_pages_cost_a_walk_and_stay():
    """Every resident pinned or referenced: the one walk finds no victim,
    the newcomer degrades a tier, the counts say where everything is."""
    ctx, store = plain_store(hot=2, warm=8, high_pct=100, low_pct=100)
    data = np.full(PB, 3, np.uint8)
    try:
        a, b = store.alloc_page(data), store.alloc_page(data, shared=True)
        store.pin(a)
        b.refs = 1
        c = store.alloc_page(data)
        assert (a.tier, b.tier, c.tier) == (Tier.HOT, Tier.HOT, Tier.WARM)
        assert places(store) == {"pages": 3, "walks": 1}
        assert store.occupancy()["host"] == {"pages": 1, "bytes": PB}
        b.refs = 0
    finally:
        store.close()
        ctx.tini()


# -- through the engine -------------------------------------------------------


def test_a_window_familys_run_with_hot_ample_walks_nothing(monkeypatch):
    import jax

    from oncilla_tpu.models import swa_moe
    from test_serving_batch_free import count_calls
    from test_swa_moe import serve

    cfg = swa_moe.SwaMoeConfig.tiny()
    params = swa_moe.init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (41, 30, 9)]
    seen = {}

    def watch(eng):
        eng.store.pages = WatchedPages()
        seen["ships"] = count_calls(monkeypatch, eng, "_ship")
        seen["chunks"] = count_calls(monkeypatch, eng, "_place_chunk")
        seen["store"], seen["kinds"] = eng.store, len(eng.kinds)

    _, meta = serve(cfg, params, prompts, [9, 6, 5], watch=watch)
    # a chunk's pages are placed together, a decode's page by its ship
    ships = len(seen["ships"]) + sum(pages for _, pages in seen["chunks"])
    assert ships > 10 and meta["window"]["pages_dropped"] > 0
    # a page a kind a page shipped, dropped window pages and all
    assert meta["places"] == {"pages": seen["kinds"] * ships,
                              "walks": 0}
    assert meta["places"]["pages"] == meta["frees"]["pages"]
    # the teardown's close() lists the pages once; nothing else walked them
    assert seen["store"].pages.walks <= 1


def test_a_conv_familys_run_with_snapshots_and_hot_ample_walks_nothing(
        monkeypatch):
    import jax

    from oncilla_tpu.models import conv_moe as cm
    from test_prefix_carry import Stack, prompts_behind
    from test_serving_batch_free import count_calls

    cfg = cm.ConvMoeConfig.tiny()
    tiny = (cfg, cm.init_params(jax.random.key(11), cfg))
    prompts = prompts_behind(cfg, 5, 3, (6, 2, 7))
    with Stack(tiny, share=True, max_active=1) as s:
        s.store.pages = WatchedPages()
        ships = count_calls(monkeypatch, s.eng, "_ship")
        allocs = count_calls(monkeypatch, s.store, "alloc_page")
        clones = count_calls(monkeypatch, s.store, "cow")
        s.run(prompts, new=6)
        meta = s.eng.metrics_meta()
        snapshots = meta["prefix"]["carry_snapshots"]
        assert snapshots >= 3 and meta["prefix"]["adoptions"] >= 2
        # every alloc_page and every clone is one placement, and no more:
        # the pages the ships and the partial publishes stored, and a
        # snapshot beside each published one
        assert meta["places"] == {"pages": len(allocs) + len(clones),
                                  "walks": 0}
        stored = len(allocs) - snapshots
        assert 0 < stored <= len(ships) + len(prompts)
        assert s.store.pages.walks == 0
        assert s.store.occupancy() == recount(s.store)
