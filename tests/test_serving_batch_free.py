"""Pages freed together leave the store together: ``TieredPageStore.
free_pages`` (one ``Ocm.free_many``, the HOT extents scrubbed a dispatch a
group, the books brought up once), the ``frees`` counter, and the engine's
two callers of it (the tick's finish and the drop of passed window pages).
CPU-only: counts and bytes, never a time."""

from __future__ import annotations

import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.core.errors import OcmInvalidHandle
from oncilla_tpu.core.hbm import _SCRUB_GROUPS
from oncilla_tpu.core.kinds import OcmKind
from oncilla_tpu.serving.engine import Request, ServingEngine
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.tiers import Tier, TieredPageStore

PB = 12 << 10            # no power of two: two fills a page on the old path
P = 4


def make_store(hot, warm=2, page_bytes=PB, **kw):
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=(hot + 2) * page_bytes))
    store = TieredPageStore(ctx, page_bytes, hot_capacity=hot,
                            warm_capacity=warm, stats=ServingStats("free"),
                            **kw)
    return ctx, store


def page_data(seed: int, nbytes: int = PB) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 256, nbytes,
                                                dtype=np.uint8)


def frees(store) -> dict:
    return store.stats.snapshot()["frees"]


def count_calls(monkeypatch, obj, name: str) -> list:
    calls = []
    inner = getattr(obj, name)

    def counted(*a, **kw):
        calls.append(a)
        return inner(*a, **kw)

    monkeypatch.setattr(obj, name, counted)
    return calls


# -- the store --------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 5, 70])
def test_free_pages_syncs_once_and_scrubs_a_group_a_dispatch(monkeypatch, n):
    # HOT twice the pages: no watermark moves any
    ctx, store = make_store(hot=2 * n + 8)
    twin_ctx, twin = make_store(hot=2 * n + 8)
    pages = [store.alloc_page(page_data(i)) for i in range(n + 2)]
    others = [twin.alloc_page(page_data(i)) for i in range(n + 2)]
    assert all(p.tier == Tier.HOT for p in pages)
    syncs = count_calls(monkeypatch, store, "_sync_stats")
    before = frees(store)
    assert before == {"pages": 0, "calls": 0, "scrub_dispatches": 0}
    store.free_pages(pages[:n])
    after = frees(store)
    assert len(syncs) == 1
    assert after["pages"] == n and after["calls"] == 1
    assert 1 <= after["scrub_dispatches"] <= -(-n // _SCRUB_GROUPS[-1]) + 2
    # the books read what n single frees leave
    for page in others[:n]:
        twin.free_page(page)
    assert frees(twin) == {"pages": n, "calls": n, "scrub_dispatches": n}
    assert store.occupancy() == twin.occupancy()
    assert store.stats.snapshot()["tier_pages"] == {
        "hbm": 2, "host": 0, "remote": 0, "frozen": 0}
    arena = ctx.device_arenas[0]
    assert arena.allocator.num_live == 2
    assert all(p.freed and p.handle.freed for p in pages[:n])
    # the survivors keep their bytes, the next tenants read zeros
    for i, page in enumerate(pages[n:], n):
        assert bytes(store.read_page(page)) == page_data(i).tobytes()
    for _ in range(min(n, 3)):
        handle = ctx.alloc(PB, OcmKind.LOCAL_DEVICE)
        assert not np.asarray(ctx.get(handle)).any()
    # freed again, or listed twice: passed over, and not counted
    store.free_pages(pages[:n])
    store.free_pages([pages[n], pages[n]])
    assert frees(store)["pages"] == n + 1 and frees(store)["calls"] == 2
    for s, c in ((store, ctx), (twin, twin_ctx)):
        s.close()
        c.tini()


def test_free_pages_refuses_a_referenced_shared_page_before_it_frees_any():
    ctx, store = make_store(hot=6)
    pages = [store.alloc_page(page_data(i)) for i in range(4)]
    pages[2].shared, pages[2].refs = True, 1
    live = ctx.device_arenas[0].allocator.bytes_live
    with pytest.raises(OcmInvalidHandle):
        store.free_pages(pages)
    assert set(store.pages) == {p.page_id for p in pages}
    assert not any(p.freed for p in pages)
    assert ctx.device_arenas[0].allocator.bytes_live == live
    assert frees(store)["calls"] == 0
    for i, page in enumerate(pages):
        assert bytes(store.read_page(page)) == page_data(i).tobytes()
    pages[2].refs = 0
    store.free_pages(pages)
    assert not store.pages and frees(store)["pages"] == 4
    store.close()
    ctx.tini()


class _HostCold:
    """A cold backend of its own (another context's host arena): what the
    store must free through the backend and not through its context."""

    def __init__(self):
        self.ctx = ocm.Ocm(config=ocm.OcmConfig(
            host_arena_bytes=1 << 20, device_arena_bytes=1 << 12))
        self.freed = 0

    def alloc(self, nbytes, kind):
        return self.ctx.alloc(nbytes, OcmKind.LOCAL_HOST)

    def free(self, handle):
        self.freed += 1
        self.ctx.free(handle)

    def put(self, handle, data, offset):
        self.ctx.put(handle, data, offset)

    def get(self, handle, nbytes, offset):
        return self.ctx.get(handle, nbytes, offset)


@pytest.mark.parametrize("cold", ["cold_sim", "backend"])
def test_free_pages_frees_pages_of_mixed_tiers(cold):
    backend = _HostCold() if cold == "backend" else None
    ctx, store = make_store(hot=2, warm=2, cold_backend=backend)
    pages = [store.alloc_page(page_data(i)) for i in range(7)]
    tiers = {p.tier for p in pages}
    assert tiers == {Tier.HOT, Tier.WARM, Tier.COLD}
    n_cold = sum(p.tier == Tier.COLD for p in pages)
    store.free_pages(pages)
    assert not store.pages and all(p.freed for p in pages)
    assert frees(store)["pages"] == 7 and frees(store)["calls"] == 1
    assert ctx.device_arenas[0].allocator.bytes_live == 0
    assert ctx.host_arena.allocator.bytes_live == 0
    assert store.stats.snapshot()["tier_pages"] == {
        "hbm": 0, "host": 0, "remote": 0, "frozen": 0}
    if backend is not None:
        assert backend.freed == n_cold
        assert backend.ctx.host_arena.allocator.bytes_live == 0
        backend.ctx.tini()
    store.close()
    ctx.tini()


def test_a_single_hot_page_and_a_moves_old_extent_cost_one_dispatch_each(
        monkeypatch):
    """``free_page`` is ``free_pages`` of one, and a demotion's release of
    the HOT extent goes the same way: one group program, where the old path
    cut 12 KiB into 8 + 4."""
    ctx, store = make_store(hot=4)
    arena = ctx.device_arenas[0]
    fills = count_calls(monkeypatch, arena, "fill_zero_many")
    singles = count_calls(monkeypatch, arena, "fill_zero")
    a, b = store.alloc_page(page_data(1)), store.alloc_page(page_data(2))
    store.free_page(a)
    assert frees(store) == {"pages": 1, "calls": 1, "scrub_dispatches": 1}
    store.demote(b, Tier.WARM)
    assert b.tier == Tier.WARM and len(fills) == 2 and not singles
    assert arena.allocator.bytes_live == 0
    assert not np.asarray(arena.buffer).any()
    assert bytes(store.read_page(b)) == page_data(2).tobytes()
    # a move frees no page
    assert frees(store)["pages"] == 1
    store.close()
    ctx.tini()


def test_close_frees_every_page_in_one_call_shared_ones_too():
    ctx, store = make_store(hot=8)
    pages = [store.alloc_page(page_data(i), shared=i < 2) for i in range(6)]
    pages[0].refs = 3
    store.close()
    assert not store.pages and frees(store) == {
        "pages": 6, "calls": 1, "scrub_dispatches": 1}
    assert ctx.device_arenas[0].allocator.bytes_live == 0
    ctx.tini()


def test_free_many_of_the_context_checks_every_handle_first():
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 16,
                                       device_arena_bytes=1 << 16))
    dev = [ctx.alloc(4096, OcmKind.LOCAL_DEVICE) for _ in range(3)]
    host = [ctx.alloc(4096, OcmKind.LOCAL_HOST) for _ in range(2)]
    ctx.put(dev[1], np.full(4096, 7, np.uint8))
    gone = ctx.alloc(4096, OcmKind.LOCAL_DEVICE)
    ctx.free(gone)
    for batch in (dev + [gone], dev + host + dev[:1], [None]):
        with pytest.raises(OcmInvalidHandle):
            ctx.free_many(batch)
        assert not any(h.freed for h in dev + host)
        assert ctx.device_arenas[0].allocator.num_live == 3
    assert np.asarray(ctx.get(dev[1])).all()
    # a size the arena was not told of: a dispatch an extent, as free()
    assert ctx.free_many(dev + host) == 3
    assert all(h.freed for h in dev + host)
    assert ctx.device_arenas[0].allocator.bytes_live == 0
    assert ctx.host_arena.allocator.bytes_live == 0
    assert ctx.free_many([]) == 0
    ctx.tini()


# -- the engine -------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from oncilla_tpu.models import LlamaConfig, init_params_host

    cfg = LlamaConfig.tiny()
    return cfg, init_params_host(0, cfg)


def build(tiny_model, **kw):
    cfg, params = tiny_model
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=1 << 20))
    store = TieredPageStore(
        ctx, ServingEngine.page_nbytes(cfg, P), hot_capacity=64,
        warm_capacity=4, stats=ServingStats("free"))
    eng = ServingEngine(params, cfg, store, None, page_tokens=P,
                        prefetch_workers=0, name="free", **kw)
    return ctx, store, eng


def test_sessions_that_end_in_one_tick_make_one_free_pages_call(
        tiny_model, monkeypatch):
    cfg, _ = tiny_model
    ctx, store, eng = build(tiny_model, max_active=4, max_batch=4)
    rng = np.random.default_rng(5)
    try:
        # two of a length end together, the third a few ticks later
        for i, (n, new) in enumerate(((13, 6), (13, 6), (13, 11))):
            eng.submit(Request(tenant=f"t{i}", max_new_tokens=new,
                               tokens=rng.integers(1, cfg.vocab, n).tolist()))
        calls = count_calls(monkeypatch, store, "free_pages")
        ended = []                    # (sessions ended, pages freed) a tick
        while eng.queue or eng.active:
            live, held = len(eng.active) + len(eng.queue), len(store.pages)
            before = frees(store)
            eng._tick()
            after = frees(store)
            gone = live - len(eng.active) - len(eng.queue)
            if gone:
                ended.append((gone, after["pages"] - before["pages"]))
                assert after["calls"] - before["calls"] == 1
                assert after["scrub_dispatches"] - before[
                    "scrub_dispatches"] == 1
            else:
                # nothing ended: the finish asks for nothing to be freed
                assert after == before and len(store.pages) >= held
        assert [g for g, _ in ended] == [2, 1]
        # a prompt of 13 and 6 tokens are 4 whole pages a session; 5 with 11
        assert [p for _, p in ended] == [8, 5]
        assert sum(1 for (pages,) in calls if pages) == 2
        assert not store.pages
        assert frees(store) == {"pages": 13, "calls": 2,
                                "scrub_dispatches": 2}
        assert len(eng.results) == 3
    finally:
        eng.close()
        store.close()
        ctx.tini()


def test_close_abandons_live_sessions_pages_in_one_call(tiny_model):
    cfg, _ = tiny_model
    ctx, store, eng = build(tiny_model, max_active=4, max_batch=4)
    rng = np.random.default_rng(6)
    for i in range(3):
        eng.submit(Request(tenant=f"t{i}", max_new_tokens=20,
                           tokens=rng.integers(1, cfg.vocab, 9 + i).tolist()))
    for _ in range(6):
        eng._tick()
    held = len(store.pages)
    assert held >= 6 and frees(store)["calls"] == 0
    eng.close()
    assert not store.pages and not eng.results
    assert frees(store) == {"pages": held, "calls": 1, "scrub_dispatches": 1}
    store.close()
    ctx.tini()


def test_dropped_window_pages_are_freed_where_they_are_dropped():
    """A family with a window kind: ``_drop_passed`` frees what one call
    dropped in one ``free_pages``, inside the chunk or the step that
    shipped (HOT's occupancy is what it was), and the finish frees the
    rest together."""
    import jax

    from oncilla_tpu.models import swa_moe
    from oncilla_tpu.utils.debug import GLOBAL_TRACER

    cfg = swa_moe.SwaMoeConfig.tiny()
    params = swa_moe.init_params(jax.random.key(3), cfg)
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, ServingEngine.page_nbytes(cfg, P),
                            hot_capacity=256, warm_capacity=4,
                            stats=ServingStats("drop"))
    eng = ServingEngine(params, cfg, store, None, page_tokens=P,
                        max_active=2, prefetch_workers=0, name="drop")
    rng = np.random.default_rng(7)
    try:
        for i, n in enumerate((41, 30)):
            eng.submit(Request(tenant=f"t{i}", max_new_tokens=9,
                               tokens=rng.integers(1, cfg.vocab, n).tolist()))
        spans0 = GLOBAL_TRACER.snapshot()
        peak = 0
        while eng.queue or eng.active:
            eng._tick()
            peak = max(peak, len(store.pages))
        meta = eng.metrics_meta()
        spans = GLOBAL_TRACER.snapshot()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    dropped = meta["window"]["pages_dropped"]
    drops = sum(spans[s]["count"] - spans0.get(s, {"count": 0})["count"]
                for s in ("prefill.drop", "step.drop"))
    assert dropped > 0 and not store.pages
    got = meta["frees"]
    shipped = 2 * meta["window"]["pages_shipped"]     # a page a kind a ship
    assert got["pages"] == shipped
    # a call a drop that dropped anything, and one a tick that ended one
    assert got["calls"] <= min(dropped, drops) + 2
    assert got["scrub_dispatches"] == got["calls"]
    # freed where dropped: the store never held every page shipped
    assert peak < shipped - dropped / 2
