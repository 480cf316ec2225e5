"""Pages written into HOT a group at a time: ``DeviceArena.write_many``,
``Ocm.put_many``, ``TieredPageStore.alloc_pages`` and the window family's
chunk that the engine hands the store together, each held to what the
one-at-a-time path gives (the same bytes, the same books); and the families
of one page a program, whose ships keep their calls, a page and a kind at a
time. On the CPU: bytes and counts, never a time."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.core.context import Ocm
from oncilla_tpu.core.hbm import _BLOCK, DeviceArena
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.tiers import Tier, TieredPageStore
from oncilla_tpu.utils.debug import Tracer

#: The store's slot, and a smaller kind's page: whole blocks, so that a
#: blocked arena takes both a group at a time.
PB = 2 * _BLOCK
SMALL = _BLOCK
ROWS = 8


def arena(layout: str, blocks: int = 64) -> DeviceArena:
    """A small arena in either layout: ``blocked`` addresses whole blocks
    as a > 2 GiB arena does (``tests/test_hbm_blocked.py`` writes one of
    those), at a size a test can hold twice."""
    a = DeviceArena(blocks * _BLOCK)
    if layout == "blocked":
        a._blocked = True
        a._buf = jax.device_put(np.zeros((blocks, _BLOCK), np.uint8),
                                a.device)
    return a


def random_rows(seed: int, n: int = ROWS, nbytes: int = PB) -> jax.Array:
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 256, (n, nbytes), dtype=np.uint8))


def whole(a: DeviceArena) -> np.ndarray:
    return np.asarray(a.buffer).reshape(-1)


# -- the memory plane ---------------------------------------------------------


@pytest.mark.parametrize("layout", ["flat", "blocked"])
@pytest.mark.parametrize("count", [1, 3, ROWS])
def test_write_many_is_single_writes(layout, count):
    """``count`` extents written a group at a time, the group padded to the
    rows' count where it is fewer: one program, and the arena byte for byte
    what ``count`` single writes leave, a neighbour between them kept."""
    group, single = arena(layout), arena(layout)
    rows = random_rows(count)
    extents = []
    for a in (group, single):
        got = []
        for i in range(count):
            got.append(a.alloc(PB))
            keeper = a.alloc(PB)
            a.write(keeper, np.full(PB, 7, np.uint8))
        extents.append(got)
    assert [e.offset for e in extents[0]] == [e.offset for e in extents[1]]
    assert group.write_many(extents[0], rows) == 1
    for i, extent in enumerate(extents[1]):
        single.write(extent, rows[i])
    np.testing.assert_array_equal(whole(group), whole(single))
    for i, extent in enumerate(extents[0]):
        np.testing.assert_array_equal(np.asarray(group.read(extent, PB)),
                                      np.asarray(rows[i]))


@pytest.mark.parametrize("layout", ["flat", "blocked"])
def test_write_many_writes_rows_narrower_than_their_extents(layout):
    """A smaller kind's rows into slots of the store's size: the row's
    bytes at the extent's start, the rest of the slot left as it was."""
    a = arena(layout)
    extents = [a.alloc(PB) for _ in range(3)]
    rows = random_rows(5, nbytes=SMALL)
    assert a.write_many(extents, rows) == 1
    for i, extent in enumerate(extents):
        got = np.asarray(a.read(extent, PB))
        np.testing.assert_array_equal(got[:SMALL], np.asarray(rows[i]))
        assert not got[SMALL:].any()


def test_a_blocked_arena_writes_what_its_group_cannot_take_one_by_one():
    """Off a block boundary, or rows that are no whole count of blocks:
    each such extent is written alone, the rest still a group, and every
    byte lands as single writes place it."""
    group, single = arena("blocked"), arena("blocked")
    for a in (group, single):
        a.alloc(512)                    # the next extent starts off a block
    off = [group.alloc(PB), group.alloc(PB)]
    twin_off = [single.alloc(PB), single.alloc(PB)]
    assert all(e.offset % _BLOCK for e in off)
    rows = random_rows(8, n=4)
    for a in (group, single):
        a.alloc(_BLOCK - 512)           # and the next on one again
    on = [group.alloc(PB + _BLOCK) for _ in range(2)]
    twin_on = [single.alloc(PB + _BLOCK) for _ in range(2)]
    assert not any(e.offset % _BLOCK for e in on)
    # two alone, two in one program
    assert group.write_many(off + on, rows) == 3
    for i, extent in enumerate(twin_off + twin_on):
        single.write(extent, rows[i])
    np.testing.assert_array_equal(whole(group), whole(single))
    # rows of a block and a half: every one alone
    ragged = jnp.asarray(np.random.default_rng(2).integers(
        1, 256, (2, _BLOCK + 512), dtype=np.uint8))
    assert group.write_many(on, ragged) == 2
    for i, extent in enumerate(twin_on):
        single.write(extent, ragged[i])
    np.testing.assert_array_equal(whole(group), whole(single))


def test_write_many_refuses_more_extents_than_rows():
    a = arena("flat")
    extents = [a.alloc(PB) for _ in range(3)]
    with pytest.raises(ValueError):
        a.write_many(extents, random_rows(0, n=2))


def device_ctx(pages: int = 32) -> Ocm:
    return ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                        device_arena_bytes=pages * PB))


def test_put_many_is_one_put_span_of_the_groups_bytes():
    ctx = device_ctx()
    try:
        ctx.tracer = Tracer()
        handles = [ctx.alloc(PB, ocm.OcmKind.LOCAL_DEVICE) for _ in range(5)]
        host = ctx.alloc(PB, ocm.OcmKind.LOCAL_HOST)
        rows = random_rows(3, n=ROWS)
        assert ctx.put_many(handles, rows) == 1
        spans = ctx.tracer.snapshot()
        assert spans["put"]["count"] == 1
        assert spans["put"]["total_bytes"] == 5 * PB
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(np.asarray(ctx.get(h)),
                                          np.asarray(rows[i]))
        # another kind among them is put as put() puts it, in its place
        assert ctx.put_many([host] + handles[:2], rows) == 1
        spans = ctx.tracer.snapshot()
        assert spans["put"]["count"] == 3
        assert spans["put"]["total_bytes"] == 8 * PB
        np.testing.assert_array_equal(np.asarray(ctx.get(host)),
                                      np.asarray(rows[0]))
        for i, h in enumerate(handles[:2]):
            np.testing.assert_array_equal(np.asarray(ctx.get(h)),
                                          np.asarray(rows[i + 1]))
    finally:
        ctx.tini()


# -- the store ----------------------------------------------------------------


def twin_stores(hot: int, warm: int = 4, arena_pages: int | None = None,
                **kw):
    out = []
    for _ in range(2):
        ctx = ocm.Ocm(config=ocm.OcmConfig(
            host_arena_bytes=1 << 20,
            device_arena_bytes=(arena_pages or hot + 2) * PB))
        out.append((ctx, TieredPageStore(ctx, PB, hot_capacity=hot,
                                         warm_capacity=warm,
                                         stats=ServingStats("group"), **kw)))
    return out


def books(store) -> dict:
    snap = store.stats.snapshot()
    return {
        "pages": [(p.page_id, p.nbytes, p.tier, p.last_use, p.shared)
                  for p in store.pages.values()],
        "count": list(store._count), "bytes": list(store._bytes),
        "occupancy": store.occupancy(), "places": snap["places"],
        "stats_occupancy": (snap["tier_pages"], snap["tier_pages_peak"],
                            snap["tier_bytes"]),
        "moves": snap["moves"], "degraded": snap["degraded"],
    }


def held(store) -> dict:
    return {p.page_id: np.asarray(store.read_page(p)).copy()
            for p in list(store.pages.values())}


def settle(grouped, single, rows_by_call):
    """Each call's rows into ``grouped`` by ``alloc_pages`` and into
    ``single`` by one ``alloc_page`` a row."""
    for rows, count, shared in rows_by_call:
        got = grouped.alloc_pages(rows, count, shared=shared)
        want = [single.alloc_page(rows[i], shared=shared)
                for i in range(count)]
        assert [p.page_id for p in got] == [p.page_id for p in want]
        assert [p.tier for p in got] == [p.tier for p in want]


@pytest.mark.parametrize("hot, calls", [
    (32, ((ROWS, False), (3, False), (ROWS, True))),    # HOT ample
    (12, ((ROWS, False), (ROWS, False), (2, False))),   # past its high mark
    (4, ((3, False), (ROWS, False))),                   # past its capacity
])
def test_alloc_pages_is_alloc_page_a_row(hot, calls):
    """Twin stores, one placing groups and one a row at a time: the same
    page ids, tiers, last_use order, books, places and occupancy, and the
    same bytes read back from every page."""
    (gctx, grouped), (sctx, single) = twin_stores(hot)
    try:
        rows_by_call = [(random_rows(i), count, shared)
                        for i, (count, shared) in enumerate(calls)]
        settle(grouped, single, rows_by_call)
        assert books(grouped) == books(single)
        got, want = held(grouped), held(single)
        assert got.keys() == want.keys()
        for pid in got:
            np.testing.assert_array_equal(got[pid], want[pid])
    finally:
        for ctx, store in ((gctx, grouped), (sctx, single)):
            store.close()
            ctx.tini()


def test_a_group_is_one_dispatch_a_call_while_hot_takes_it():
    (ctx, store), (tctx, twin) = twin_stores(32)
    try:
        store.alloc_pages(random_rows(0), ROWS)
        store.alloc_pages(random_rows(1, nbytes=SMALL), 5)
        snap = store.stats.snapshot()
        assert snap["hot_writes"] == {"pages": ROWS + 5, "dispatches": 2}
        assert snap["places"] == {"pages": ROWS + 5, "walks": 0}
        assert {p.tier for p in store.pages.values()} == {Tier.HOT}
        # pages placed one at a time write no group
        twin.alloc_page(random_rows(2)[0])
        assert "hot_writes" not in twin.stats.snapshot()
    finally:
        for c, s in ((ctx, store), (tctx, twin)):
            s.close()
            c.tini()


def test_past_the_high_mark_the_group_falls_back_and_writes_none():
    """HOT would pass its high mark: the rows go page by page (some demote
    others), no group write is counted, nothing leaks."""
    (ctx, store), _ = twin_stores(10)
    try:
        store.alloc_pages(random_rows(0), ROWS)
        assert store.stats.snapshot()["hot_writes"]["pages"] == ROWS
        pages = store.alloc_pages(random_rows(1), ROWS)
        assert len(pages) == ROWS
        assert store.stats.snapshot()["hot_writes"] == {
            "pages": ROWS, "dispatches": 1}
        assert store.stats.snapshot()["moves"]["demote"] > 0
        hot = [p for p in store.pages.values() if p.tier == Tier.HOT]
        dev = ctx.device_arenas[0].allocator
        dev.check_live([p.handle.extent for p in hot])
        assert dev.bytes_live == len(hot) * PB
    finally:
        store.close()
        ctx.tini()


def test_an_arena_that_refuses_mid_group_leaves_nothing_behind(monkeypatch):
    """HOT's capacity says yes but its arena holds fewer slots: the extents
    taken are given back, the rows go page by page (the rest degrade a
    tier, as alloc_page degrades them), and the arena holds exactly the HOT
    pages' extents."""
    (ctx, store), (tctx, twin) = twin_stores(16, warm=16, arena_pages=5)
    try:
        taken = []
        alloc = ctx.alloc

        def counted(*a, **kw):
            taken.append(a)
            return alloc(*a, **kw)

        monkeypatch.setattr(ctx, "alloc", counted)
        rows = random_rows(4)
        settle(store, twin, [(rows, ROWS, False)])
        assert books(store) == books(twin)
        assert "hot_writes" not in store.stats.snapshot()
        assert store.stats.snapshot()["degraded"]["capacity_free"] > 0
        hot = [p for p in store.pages.values() if p.tier == Tier.HOT]
        assert len(hot) == 5 and len(taken) > ROWS
        dev = ctx.device_arenas[0].allocator
        dev.check_live([p.handle.extent for p in hot])
        assert dev.bytes_live == 5 * PB
        live = {h.alloc_id for h in ctx._allocs.values()}
        assert live == {p.handle.alloc_id for p in store.pages.values()}
    finally:
        for c, s in ((ctx, store), (tctx, twin)):
            s.close()
            c.tini()


def test_alloc_pages_refuses_a_row_past_the_slot():
    (ctx, store), _ = twin_stores(8)
    try:
        with pytest.raises(ValueError):
            store.alloc_pages(random_rows(0, n=2, nbytes=PB + 512))
        with pytest.raises(ValueError):
            store.alloc_pages(random_rows(0, n=2), 3)
        assert store.alloc_pages(random_rows(0, n=2), 0) == []
        assert not store.pages
    finally:
        store.close()
        ctx.tini()


# -- through the engine -------------------------------------------------------


def page_at_a_time(eng):
    """The chunk's pages shipped as the page-at-a-time ship stored them:
    a pack a page, one ``alloc_page`` a kind."""
    from oncilla_tpu.serving.engine import _pack_pages_jit

    def place(made, pages):
        placed = []
        for j in range(pages):
            packed = _pack_pages_jit(
                tuple(made[j][leaves] for leaves in eng._kind_leaves),
                eng.store_dtype)
            placed.append(tuple(eng.store.alloc_page(rows)
                                for rows in packed))
        for kind in eng.kinds:
            if kind.window is not None:
                eng.stats.note_window(shipped=pages)
        return placed

    eng._place_chunk = place


def record_pages(stored):
    """After every chunk, each session's pages: (tenant, kind, bytes)."""
    def watch(eng):
        chunk = eng._prefill_chunk

        def recorded(sess, most=1):
            pages = chunk(sess, most)
            stored.append([(sess.req.tenant, e.kind,
                            np.asarray(eng.store.read_page(e.page)).copy())
                           for e in sess.entries])
            return pages

        eng._prefill_chunk = recorded
    return watch


@pytest.mark.parametrize("cfg_name, hot", [
    ("tiny", 256), ("tiny_softmax", 256), ("tiny", 6)])
def test_a_window_familys_chunk_stores_what_the_page_at_a_time_ship_did(
        cfg_name, hot):
    """The same requests through a window-family engine, its chunks placed
    together and page at a time: the same tokens and logits, the same bytes
    in every page after every chunk, those bytes the page's own arrays
    packed, and the group counted only where it ran (HOT tight: pages
    demote and the store falls back)."""
    from oncilla_tpu.models import swa_moe as sm
    from oncilla_tpu.serving.engine import _pack_pages_jit
    from test_swa_moe import serve

    cfg = getattr(sm.SwaMoeConfig, cfg_name)()
    params = sm.init_params(jax.random.key(7), cfg)
    rng = np.random.default_rng(3)
    lens, new = (61, 23, 45, 38), (5, 9, 4, 6)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    runs = []
    for grouped in (True, False):
        stored, checked = [], []

        def watch(eng, grouped=grouped, stored=stored, checked=checked):
            record_pages(stored)(eng)
            if not grouped:
                page_at_a_time(eng)
                return
            place = eng._place_chunk

            def place_checked(made, pages):
                placed = place(made, pages)
                for j, kinds in enumerate(placed):
                    for k, page in enumerate(kinds):
                        want = _pack_pages_jit(
                            (made[j][eng._kind_leaves[k]],),
                            eng.store_dtype)[0]
                        np.testing.assert_array_equal(
                            np.asarray(eng.store.read_page(page)),
                            np.asarray(want))
                        checked.append(page.page_id)
                return placed

            eng._place_chunk = place_checked

        results, meta = serve(cfg, params, prompts, new, hot=hot,
                              max_active=4, max_batch=4, watch=watch)
        runs.append((results, meta, stored, checked))
    (res_g, meta_g, stored_g, checked), (res_s, meta_s, stored_s, _) = runs
    assert checked
    for tenant, res in res_g.items():
        assert res.out_tokens == res_s[tenant].out_tokens
        np.testing.assert_array_equal(np.stack(res.out_logits),
                                      np.stack(res_s[tenant].out_logits))
    assert len(stored_g) == len(stored_s) == meta_g["batch"][
        "prefill_chunks"]
    for got, want in zip(stored_g, stored_s):
        assert [(t, k) for t, k, _ in got] == [(t, k) for t, k, _ in want]
        for (_, _, g), (_, _, w) in zip(got, want):
            np.testing.assert_array_equal(g, w)
    for key in ("window", "prefill"):
        assert meta_g[key] == meta_s[key]
    # HOT tight, the order of a chunk's placements (a kind's pages, then
    # the next kind's) moves which pages demote: the counts of pages hold
    for key in ("places", "frees"):
        assert meta_g[key]["pages"] == meta_s[key]["pages"]
    assert "hot_writes" not in meta_s
    if hot == 256:
        assert meta_g["places"] == meta_s["places"]
        assert meta_g["frees"] == meta_s["frees"]
        writes = meta_g["hot_writes"]
        assert writes["pages"] == 2 * meta_g["prefill"]["pages"]
        assert writes["dispatches"] == 2 * meta_g["batch"]["prefill_chunks"]
    else:
        assert meta_g["moves"]["demote"] > 0


# -- the families of one page a program -----------------------------------------


@pytest.mark.parametrize("name", ["dense", "latent"])
def test_a_one_page_familys_ship_keeps_its_calls(monkeypatch, name):
    """A dense and a latent engine, every ship logged with the calls made
    inside it: one ship a page, and in it one ``alloc_page``, one
    ``Ocm.put`` and one ``DeviceArena.write`` a kind, then the tail reset
    where the session holds it; no group write anywhere, and no
    ``hot_writes`` in the engine's counters."""
    from oncilla_tpu.serving import engine as eng_mod
    from test_swa_moe import P, _family_case, serve

    cfg, params = _family_case(name)
    log = []

    def logged(cls, attr, tag):
        inner = getattr(cls, attr)

        def call(*a, **kw):
            log.append(tag)
            return inner(*a, **kw)

        monkeypatch.setattr(cls, attr, call)

    logged(TieredPageStore, "alloc_page", "alloc_page")
    logged(TieredPageStore, "alloc_pages", "alloc_pages")
    logged(Ocm, "put", "put")
    logged(Ocm, "put_many", "put_many")
    logged(DeviceArena, "write", "write")
    logged(DeviceArena, "write_many", "write_many")
    logged(eng_mod._Session, "reset_tail", "reset_tail")
    seated = []
    ship = eng_mod.ServingEngine._ship

    def shipped(self, sess):
        seated.append(sess.seat is not None)
        log.append("ship")
        ship(self, sess)
        log.append("end")

    monkeypatch.setattr(eng_mod.ServingEngine, "_ship", shipped)
    lens = (29, 13, 42)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    _, meta = serve(cfg, params, prompts, (6, 9, 5), max_active=3,
                    max_batch=2)
    assert "hot_writes" not in meta
    assert not {"alloc_pages", "put_many", "write_many"} & set(log)
    ships, at = [], 0
    while "ship" in log[at:]:
        start = log.index("ship", at)
        # nothing is written outside a ship (HOT ample: no moves)
        assert not {"alloc_page", "put", "write"} & set(log[at:start])
        end = log.index("end", start)
        ships.append(log[start + 1:end])
        at = end + 1
    assert not {"alloc_page", "put", "write"} & set(log[at:])
    for calls, seat in zip(ships, seated):
        assert calls == ["alloc_page", "put", "write"] + (
            [] if seat else ["reset_tail"])
    pages = sum(n // P for n in lens)
    assert len(ships) >= pages and meta["places"]["pages"] == len(ships)
    assert meta["prefill"]["pages"] == meta["batch"]["prefill_chunks"] == (
        pages)
