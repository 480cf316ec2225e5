"""The fused step's page pool is brought up to a batch in a few dispatches,
for every family alike (``ServingEngine._kind_pool``): a crossing of a
power-of-two row count carries the rows over on the device in one gather,
and a tick's new rows are written in power-of-two groups of up to
``_POOL_GROUP`` a dispatch.

Every test runs for the four served families (dense K/V, latent, the
delta-rule family with a carry, the window family whose page has two
kinds) on their tiny float32 configurations. The pool is a cache of the
entries' arrays: whatever slot a page lies in, the step reads the same
bytes, so the tokens and the logits of a schedule are those of the same
schedule with the pool written one row a dispatch into a fresh pool at
every crossing, which is kept here as the reference (:func:`row_a_dispatch`).
CPU-only (conftest pins the backend).
"""

from __future__ import annotations

import contextlib
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oncilla_tpu as ocm
import oncilla_tpu.serving.engine as engine_mod
from oncilla_tpu.core.hbm import _pow2_chunks
from oncilla_tpu.serving.engine import Request, ServingEngine, _pow2
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.tiers import TieredPageStore

P = 4
G = engine_mod._POOL_GROUP
FAMILIES = ["dense", "latent", "kda", "swa"]


@functools.cache
def model(family: str):
    """The tiny configuration of a family and seeded weights."""
    if family == "dense":
        from oncilla_tpu.models import LlamaConfig, init_params_host

        cfg = LlamaConfig.tiny()
        return cfg, init_params_host(0, cfg)
    if family == "latent":
        from oncilla_tpu.models import latent_moe as mod

        cfg = mod.LatentMoeConfig.tiny()
    elif family == "kda":
        from oncilla_tpu.models import kda_latent as mod

        cfg = mod.KdaLatentConfig.tiny()
    else:
        from oncilla_tpu.models import swa_moe as mod

        cfg = mod.SwaMoeConfig.tiny()
    return cfg, mod.init_params(jax.random.key(3), cfg)


@contextlib.contextmanager
def engine(family: str, *, page_tokens=P, max_active=4, max_batch=None):
    cfg, params = model(family)
    pb = ServingEngine.page_nbytes(cfg, page_tokens)
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, pb, hot_capacity=96, warm_capacity=4,
                            stats=ServingStats(family))
    try:
        eng = ServingEngine(params, cfg, store, None,
                            page_tokens=page_tokens, max_active=max_active,
                            max_batch=max_batch, prefetch_workers=0,
                            name=family, keep_logits=True)
        try:
            yield eng
        finally:
            eng.close()
    finally:
        store.close()
        ctx.tini()


def pages(eng, k: int, n: int, first: int) -> dict:
    """``n`` pages of kind ``k`` with values of their own, by the key the
    engine would give them."""
    rng = np.random.default_rng(1000 * k + first)
    dt = jnp.dtype(eng.cfg.dtype)
    shapes = eng._leaf_shapes[eng._kind_leaves[k]]
    return {(first + i, 0): tuple(jnp.asarray(rng.standard_normal(s), dt)
                                  for s in shapes)
            for i in range(n)}


def pool_rows(eng, k: int) -> list:
    return [np.asarray(leaf) for leaf in eng._pool[k]]


def assert_rows_hold(eng, k: int, rows: dict) -> None:
    """Every key of ``rows`` has a row of its own, bitwise its page."""
    slots = eng._pool_slots[k]
    assert len(set(slots.values())) == len(slots)
    assert not set(slots.values()) & set(eng._pool_free[k])
    pool = pool_rows(eng, k)
    for key, arrays in rows.items():
        for leaf, page in zip(pool, arrays):
            assert np.array_equal(leaf[slots[key]], np.asarray(page)[:, 0])


class Dispatches:
    """Count what an engine hands its two pool programs over the pool
    itself (not over the scratch zeros of ``_warm_pool``)."""

    def __init__(self, monkeypatch, eng):
        self.slots: list = []       # of every group write
        self.gathers: list = []     # (rows before, rows after)
        self.warming = False
        write, gather = engine_mod._pool_write_jit, engine_mod._pool_gather_jit
        warm = eng._warm_pool

        def writing(pool, group, slots):
            assert len(group) == len(slots) <= G
            assert len(group) == _pow2(len(group))
            assert slots.dtype == np.int32 and isinstance(slots, np.ndarray)
            if not self.warming:
                self.slots.append(slots.tolist())
            return write(pool, group, slots)

        def gathering(pool, idx):
            assert idx.dtype == np.int32 and isinstance(idx, np.ndarray)
            if not self.warming:
                self.gathers.append((pool[0].shape[0], len(idx)))
            return gather(pool, idx)

        def warming(k, capacity):
            self.warming = True
            try:
                warm(k, capacity)
            finally:
                self.warming = False

        monkeypatch.setattr(engine_mod, "_pool_write_jit", writing)
        monkeypatch.setattr(engine_mod, "_pool_gather_jit", gathering)
        eng._warm_pool = warming

    def clear(self):
        self.slots, self.gathers = [], []


def pool_counters(eng) -> dict:
    snap = eng.stats.snapshot()
    return {**snap["pool"], **snap["pool_dispatches"]}


# -- the unit: _kind_pool over pages made here --------------------------------


@pytest.mark.parametrize("n", [1, 3, 7, G, G + 1, 3 * G + 2])
@pytest.mark.parametrize("family", FAMILIES)
def test_new_rows_go_a_group_a_dispatch(family, n, monkeypatch):
    """A tick with ``n`` new rows takes the power-of-two groups of ``n``
    (``ceil(n / G)`` dispatches and at most ``log2 G`` more) whatever ``n``
    is, and one gather more when the row count crosses a power of two; each
    new row is written once, and no other row is touched."""
    with engine(family) as eng:
        seen = Dispatches(monkeypatch, eng)
        for k in range(len(eng.kinds)):
            base = pages(eng, k, 5, first=100)
            eng._kind_pool(k, base, [list(base)])
            assert eng._pool[k][0].shape[0] == 8
            before, slots0 = pool_rows(eng, k), dict(eng._pool_slots[k])
            c0 = pool_counters(eng)
            seen.clear()
            rows = {**base, **pages(eng, k, n, first=200)}
            table = eng._kind_pool(k, rows, [list(rows)])
            capacity = _pow2(5 + n)
            assert eng._pool[k][0].shape[0] == capacity
            groups = _pow2_chunks(n, G)
            assert [len(group) for group in seen.slots] == groups
            assert -(-n // G) <= len(groups) < -(-n // G) + G.bit_length()
            assert seen.gathers == ([(8, capacity)] if capacity != 8 else [])
            assert_rows_hold(eng, k, rows)
            slots = eng._pool_slots[k]
            assert table[0, :len(rows)].tolist() == [slots[key]
                                                     for key in rows]
            # the rows written are the new keys', each once: no carried row
            fresh = [slots[key] for key in list(rows)[5:]]
            assert [s for group in seen.slots for s in group] == fresh
            if capacity == 8:
                assert {key: slots[key] for key in base} == slots0
                after = pool_rows(eng, k)
                rest = [r for r in range(8) if r not in fresh]
                for a, b in zip(before, after):
                    assert np.array_equal(a[rest], b[rest])
            c1 = pool_counters(eng)
            assert {key: c1[key] - c0[key] for key in c1} == {
                "rows_reused": 5, "rows_written": n,
                "rebuilds": int(capacity != 8),
                "group_writes": len(groups), "gathers": int(capacity != 8),
                "rows_carried": 5 if capacity != 8 else 0}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_shrink_keeps_the_batchs_rows_and_writes_only_the_new(
        family, monkeypatch):
    """After a growth and after a shrink every row the table names is
    bitwise its page, and ``rows_written`` counts the keys that had no row
    only: on a shrink the batch's keys are renumbered under the new
    capacity, least recently seated first, and the others lose their rows."""
    with engine(family) as eng:
        seen = Dispatches(monkeypatch, eng)
        for k in range(len(eng.kinds)):
            first = pages(eng, k, 3, first=100)
            eng._kind_pool(k, first, [list(first)])
            grown = {**first, **pages(eng, k, 17, first=200)}
            eng._kind_pool(k, grown, [list(grown)])
            assert eng._pool[k][0].shape[0] == 32
            assert_rows_hold(eng, k, grown)
            # Seven of the twenty stay (some from high rows), two are new.
            stay = list(grown)[1::3]
            assert max(eng._pool_slots[k][key] for key in stay) >= 16
            batch = {**{key: grown[key] for key in stay},
                     **pages(eng, k, 2, first=300)}
            c0 = pool_counters(eng)
            seen.clear()
            eng._kind_pool(k, batch, [list(batch)[:4], list(batch)[4:]])
            assert seen.gathers == [(32, 16)] and len(seen.slots) == 1
            assert len(seen.slots[0]) == 2
            assert_rows_hold(eng, k, batch)
            slots = eng._pool_slots[k]
            assert list(slots) == list(batch)
            assert sorted(slots.values()) == list(range(9))
            assert set(seen.slots[0]) == {slots[key]
                                          for key in list(batch)[7:]}
            c1 = pool_counters(eng)
            assert {key: c1[key] - c0[key] for key in c1} == {
                "rows_reused": 7, "rows_written": 2, "rebuilds": 1,
                "group_writes": 1, "gathers": 1, "rows_carried": 7}
            # a key that lost its row is written again when it comes back
            back = {**batch, list(grown)[0]: grown[list(grown)[0]]}
            eng._kind_pool(k, back, [list(back)])
            assert_rows_hold(eng, k, back)
            c2 = pool_counters(eng)
            assert c2["rows_written"] - c1["rows_written"] == 1
            assert c2["rebuilds"] == c1["rebuilds"]


@pytest.mark.parametrize("family", FAMILIES)
def test_no_program_is_built_once_a_capacity_has_been_reached(family):
    """When a capacity is first reached, the group writes and the gathers
    of it and of its neighbours run on scratch zeros: the first write in
    place, the first crossing either way and the first write after it
    build nothing, and what a crossing does build is the next capacity's.
    (Page sizes no other test uses, a family its own: shapes that are not
    in the programs' process-wide caches yet.)"""
    write, gather = engine_mod._pool_write_jit, engine_mod._pool_gather_jit

    def built():
        return write._cache_size(), gather._cache_size()

    sizes = G.bit_length()      # group sizes: 1, 2, 4 ... G
    with engine(family, page_tokens=5 + 2 * FAMILIES.index(family)) as eng:
        for k in range(len(eng.kinds)):
            w, g = built()
            base = pages(eng, k, 5, first=100)
            eng._kind_pool(k, base, [list(base)])       # the first pool: 8
            # the group writes at 4, 8 and 16, the gathers 4 <-> 8 <-> 16
            assert built() == (w + 3 * sizes, g + 4)
            more = {**base, **pages(eng, k, 2, first=200)}
            eng._kind_pool(k, more, [list(more)])       # in place
            assert built() == (w + 3 * sizes, g + 4)
            up = {**more, **pages(eng, k, 3, first=300)}
            eng._kind_pool(k, up, [list(up)])           # 8 -> 16, and writes
            assert eng._pool[k][0].shape[0] == 16
            # what 16 may cross to next: the writes at 32, 16 <-> 32
            assert built() == (w + 4 * sizes, g + 6)
            eng._kind_pool(k, base, [list(base)])       # 16 -> 8
            assert built() == (w + 4 * sizes, g + 6)
            few = dict(list(base.items())[:3])
            few.update(pages(eng, k, 1, first=400))
            eng._kind_pool(k, few, [list(few)])         # 8 -> 4, and writes
            assert eng._pool[k][0].shape[0] == 4
            # the writes at 2, 2 <-> 4
            assert built() == (w + 5 * sizes, g + 8)
            assert_rows_hold(eng, k, few)


# -- a schedule through the engine, against one row a dispatch ----------------


@partial(jax.jit, donate_argnums=(0,))
def write_row(pool: tuple, page: tuple, slot: jax.Array) -> tuple:
    """One page into one row: the program every family had of its own."""
    return tuple(
        jax.lax.dynamic_update_slice(rows, leaf[None, :, 0],
                                     (slot, 0, 0, 0, 0))
        for rows, leaf in zip(pool, page))


def row_a_dispatch(eng):
    """Put the pool's former upkeep in the engine's place: a crossing makes
    a pool of zeros and writes every page of the batch into it, and every
    page goes in a dispatch of its own."""

    def kind_pool(k, rows, keys):
        max_pages = max((len(t) for t in keys), default=0)
        mp = _pow2(max_pages) if max_pages else 0
        capacity = _pow2(len(rows)) if rows else 1
        if eng._pool[k] is None or eng._pool[k][0].shape[0] != capacity:
            eng._pool[k] = eng._zero_pool(k, capacity)
            eng._pool_slots[k] = {}
            eng._pool_free[k] = list(range(capacity - 1, -1, -1))
        slots, free = eng._pool_slots[k], eng._pool_free[k]
        fresh = []
        for key in rows:
            if key in slots:
                slots[key] = slots.pop(key)
            else:
                fresh.append(key)
        for key in fresh:
            slot = free.pop() if free else slots.pop(next(iter(slots)))
            eng._pool[k] = write_row(eng._pool[k], rows[key], np.int32(slot))
            slots[key] = slot
        table = np.zeros((len(keys), mp), np.int32)
        for b, trow in enumerate(keys):
            table[b, :len(trow)] = [slots[key] for key in trow]
        return table

    eng._kind_pool = kind_pool


def watch_rows(eng, log: list):
    """After every ``_batch_pool``: each row a table names is bitwise the
    arrays of the entry it stands for; log each kind's capacity and what
    the tick wrote."""
    inner = eng._batch_pool

    def watched(batch):
        held = [set(slots) for slots in eng._pool_slots]
        c0 = pool_counters(eng)
        pools, tables, keys = inner(batch)
        c1 = pool_counters(eng)
        fresh = 0
        for k, table in enumerate(tables):
            pool = pool_rows(eng, k)
            names = set()
            for b, sess in enumerate(batch):
                live = [e for e in sess.entries
                        if e.kind == k and not e.pending_fill]
                assert len(live) == len(keys[k][b])
                for i, e in enumerate(live):
                    key = (e.page.page_id, e.version)
                    names.add(key)
                    assert eng._pool_slots[k][key] == table[b, i]
                    for leaf, mine in zip(pool, e.arrays):
                        assert np.array_equal(leaf[table[b, i]],
                                              np.asarray(mine)[:, 0])
            fresh += len(names - held[k])
        # what was written is what the pools did not hold, crossing or not
        assert c1["rows_written"] - c0["rows_written"] == fresh
        log.append([pool[0].shape[0] for pool in pools])
        return pools, tables, keys

    eng._batch_pool = watched


# Prompt lengths, new tokens: three sessions at a time of six, long ones
# beside short ones, so that the distinct pages of the batch pass a power
# of two upwards as sessions grow and are admitted, and downwards as the
# long ones finish.
LENGTHS = (30, 5, 13, 22, 3, 9)
NEW = (14, 6, 21, 5, 12, 9)


def run_schedule(family: str, setup) -> tuple:
    cfg, _ = model(family)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, getattr(cfg, "vocab", None)
                            or cfg.vocab_size, n).tolist() for n in LENGTHS]
    with engine(family, max_active=3, max_batch=3) as eng:
        setup(eng)
        for i, (p, n) in enumerate(zip(prompts, NEW)):
            eng.submit(Request(tenant=f"t{i}", tokens=p, max_new_tokens=n))
        results = {r.tenant: r for r in eng.run()}
        return results, eng.metrics_meta()


@pytest.mark.parametrize("family", FAMILIES)
def test_a_schedule_that_crosses_both_ways_yields_the_row_a_dispatch_tokens(
        family):
    caps: list = []
    got, meta = run_schedule(family, lambda eng: watch_rows(eng, caps))
    want, old = run_schedule(family, row_a_dispatch)
    firsts = [c[0] for c in caps]
    steps = list(zip(firsts, firsts[1:]))
    assert any(a < b for a, b in steps) and any(a > b for a, b in steps)
    assert meta["pool_dispatches"]["gathers"] >= 2
    assert meta["pool_dispatches"]["rows_carried"] > 0
    assert meta["pool"]["rows_written"] >= (
        meta["pool_dispatches"]["group_writes"])
    assert sorted(got) == sorted(want) == [f"t{i}" for i in range(6)]
    for tenant, res in got.items():
        assert res.out_tokens == want[tenant].out_tokens
        assert len(res.out_tokens) == NEW[int(tenant[1:])]
        for a, b in zip(res.out_logits, want[tenant].out_logits):
            assert np.array_equal(a, b)
    assert meta["batch"]["steps"] == old["batch"]["steps"]
