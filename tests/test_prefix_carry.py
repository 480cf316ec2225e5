"""Prefix reuse over a recurrent carry (``serving/prefix.py``,
``serving/engine.py``): a shared extent of a family with a carry keeps a
snapshot of the publisher's carry beside its page, in a slot of the same
store, and an adopter goes on from a copy of it. Driven with the gated
short-convolution family (``models/conv_moe.py``) at a tiny size on the CPU in
float32: a snapshot is then as large as a page (here 2 KiB both)."""

import jax
import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.models import conv_moe as cm
from oncilla_tpu.serving.engine import Request, ServingEngine
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.prefix import PrefixCache
from oncilla_tpu.serving.tiers import Tier, TieredPageStore
from oncilla_tpu.utils.debug import GLOBAL_TRACER

P = 4   # page tokens

# Adoption changes which program made a position's K and V and carry (the
# publisher's page program, not this session's) and nothing of the
# arithmetic: what is left is float32 rounding, as between any two batch
# shapes.
ROUNDING = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = cm.ConvMoeConfig.tiny()
    return cfg, cm.init_params(jax.random.key(11), cfg)


class Stack:
    """An engine over a store, with or without the prefix cache; a context
    manager that closes what it opened."""

    def __init__(self, tiny, *, share=True, hot=256, warm=8, max_active=4,
                 max_batch=None, frozen=None):
        self.cfg, self.params = tiny
        pb = ServingEngine.page_nbytes(self.cfg, P)
        self.ctx = ocm.Ocm(config=ocm.OcmConfig(
            host_arena_bytes=1 << 20, device_arena_bytes=4 << 20))
        self.store = TieredPageStore(
            self.ctx, pb, hot_capacity=hot, warm_capacity=warm,
            stats=ServingStats("carry"), frozen_backend=frozen)
        self.prefix = PrefixCache(self.store, P) if share else None
        try:
            self.eng = ServingEngine(
                self.params, self.cfg, self.store, self.prefix,
                page_tokens=P, max_active=max_active, max_batch=max_batch,
                prefetch_workers=0, name="carry", keep_logits=True)
        except BaseException:
            self.store.close()
            self.ctx.tini()
            raise

    def run(self, prompts, new=5):
        for i, p in enumerate(prompts):
            self.eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                                    max_new_tokens=new))
        return {r.tenant: r for r in self.eng.run()}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.eng.close()
        self.store.close()
        self.ctx.tini()


def logits_of(results, i=0):
    return np.stack(results[f"t{i}"].out_logits)


def cache_off(tiny, prompts, new=5):
    with Stack(tiny, share=False) as s:
        return s.run(prompts, new)


def prompts_behind(cfg, seed, shared_pages, tails):
    rng = np.random.default_rng(seed)
    base = rng.integers(1, cfg.vocab, shared_pages * P).tolist()
    return [base + rng.integers(1, cfg.vocab, n).tolist() for n in tails]


# -- adoption gives the logits of the same request with the cache off --------------


def test_adopting_a_full_page_chain_changes_no_logit(tiny):
    cfg, _ = tiny
    first, second = prompts_behind(cfg, 1, 3, (5, 7))
    want = cache_off(tiny, [second])
    with Stack(tiny) as s:
        s.run([first])                       # publishes the three pages
        before = s.eng.metrics_meta()["prefix"]
        got = s.run([second])
        after = s.eng.metrics_meta()["prefix"]
    res = got["t0"]
    assert res.prefix_tokens_reused == 3 * P
    assert res.out_tokens == want["t0"].out_tokens
    np.testing.assert_allclose(logits_of(got), logits_of(want),
                               atol=ROUNDING)
    # one adoption of the chain, ONE restore: from the last extent adopted
    assert after["adoptions"] - before["adoptions"] == 1
    assert after["carry_restores"] - before["carry_restores"] == 1


def test_adopting_a_partial_tail_changes_no_logit(tiny):
    """A second request with the SAME prompt adopts the first's partial
    tail copy-on-write, all but its last token, and goes on from the carry
    as it stood BEFORE that token: the snapshot a partial extent keeps."""
    cfg, _ = tiny
    prompt, = prompts_behind(cfg, 2, 2, (3,))
    want = cache_off(tiny, [prompt])
    with Stack(tiny) as s:
        s.run([prompt])
        cow0 = s.eng.metrics_meta()["prefix"]["cow"]
        got = s.run([prompt])
        meta = s.eng.metrics_meta()["prefix"]
    res = got["t0"]
    assert meta["cow"] == cow0 + 1
    assert res.prefix_tokens_reused == 2 * P + 2
    assert res.out_tokens == want["t0"].out_tokens
    np.testing.assert_allclose(logits_of(got), logits_of(want),
                               atol=ROUNDING)


@pytest.mark.parametrize("tail", [1, 2, P - 1, P, P + 1])
def test_every_tail_length_adopts_what_it_can_and_is_the_cache_off_request(
        tiny, tail):
    """A prompt of one token past the shared pages, of ``K - 1``, of a page
    less one, of a whole page (which the page program takes: a full page's
    snapshot stands after its last token, where this adopter cannot resume)
    and of a page and one."""
    cfg, _ = tiny
    prompt, = prompts_behind(cfg, 3 + tail, 2, (tail,))
    want = cache_off(tiny, [prompt])
    with Stack(tiny) as s:
        s.run([prompt])
        got = s.run([prompt])
        cow = s.eng.metrics_meta()["prefix"]["cow"]
    assert got["t0"].out_tokens == want["t0"].out_tokens
    np.testing.assert_allclose(logits_of(got), logits_of(want),
                               atol=ROUNDING)
    # a CoW adoption where a partial tail of two tokens or more was left
    assert cow == (1 if tail % P > 1 else 0)
    reused = got["t0"].prefix_tokens_reused
    assert reused == {1: 2 * P, 2: 2 * P + 1, P - 1: 2 * P + P - 2,
                      P: 2 * P, P + 1: 3 * P}[tail]


def test_sessions_that_adopt_while_others_decode_are_the_cache_off_requests(
        tiny):
    cfg, _ = tiny
    prompts = prompts_behind(cfg, 9, 3, (5, 11, 2 * P, 3, P, 13))
    prompts += prompts[:2]
    want = cache_off(tiny, prompts, new=6)
    with Stack(tiny, max_active=3, max_batch=2) as s:
        got = s.run(prompts, new=6)
        meta = s.eng.metrics_meta()
    for i in range(len(prompts)):
        assert got[f"t{i}"].out_tokens == want[f"t{i}"].out_tokens
        np.testing.assert_allclose(logits_of(got, i), logits_of(want, i),
                                   atol=ROUNDING)
    assert sum(r.prefix_tokens_reused for r in got.values()) > 0
    prefix = meta["prefix"]
    assert prefix["adoptions"] == prefix["carry_restores"] > 0


# -- the snapshot lives in the store, beside its page --------------------------------


def test_two_publishers_of_one_prefix_leave_one_page_and_one_snapshot(tiny):
    """Two sessions with one prompt prefill in lockstep: both compute each
    page, the second to publish loses, and its page AND its snapshot go
    back to the store."""
    cfg, _ = tiny
    prompt, = prompts_behind(cfg, 4, 2, (2,))
    with Stack(tiny, max_active=2, max_batch=2) as s:
        s.run([prompt, prompt], new=3)
        extents = s.prefix.extents()
        meta = s.eng.metrics_meta()
        # 2 full pages and the partial tail: one extent each
        assert len(extents) == 3 == meta["prefix"]["extents"]
        assert all(e.carry is not None and e.carry.shared and e.page.shared
                   for e in extents)
        assert len({e.page.page_id for e in extents}
                   | {e.carry.page_id for e in extents}) == 6
        # nothing else is left in the store: the losers' copies are freed
        assert set(s.store.pages) == ({e.page.page_id for e in extents}
                                      | {e.carry.page_id for e in extents})
        assert meta["tier_pages"]["hbm"] == 6
        # more snapshots were taken than kept; the gauge counts the kept
        assert meta["prefix"]["carry_snapshots"] > 3
        nbytes = extents[0].carry.nbytes
        assert nbytes == 4 * 2 * cfg.hidden_size * 4 == s.store.page_bytes
        assert meta["prefix"]["carry_bytes"] == 3 * nbytes


def test_acquire_and_release_keep_both_and_a_sweep_frees_both(tiny):
    cfg, _ = tiny
    first, second = prompts_behind(cfg, 5, 2, (3, 2))
    with Stack(tiny, max_active=2) as s:
        s.eng.submit(Request("a", first, 8))
        s.eng.submit(Request("b", second, 8))
        for _ in range(4):
            s.eng._tick()
        chain = s.prefix.match(first[:2 * P])[0]
        assert len(chain) == 2
        # both sessions hold the two shared pages: page and snapshot count
        # their references alike, and neither is a victim while referenced
        assert [e.page.refs for e in chain] == [2, 2]
        assert [e.carry.refs for e in chain] == [2, 2]
        assert not [p for p in s.store._victims(Tier.HOT)
                    if p.page_id in {e.carry.page_id for e in chain}]
        # each extra reference saved a page and a snapshot
        assert s.prefix.shared_bytes() == sum(
            e.page.nbytes + e.carry.nbytes for e in chain)
        assert s.eng.metrics_meta()["prefix"]["shared_bytes_live"] == (
            s.prefix.shared_bytes())
        with pytest.raises(Exception, match="shared page"):
            s.store.free_page(chain[0].carry)
        s.eng.run()
        # released to zero: retained, unreferenced, both evictable
        extents = s.prefix.extents()
        assert all(e.page.refs == 0 and e.carry.refs == 0 for e in extents)
        assert s.prefix.shared_bytes() == 0
        assert s.eng.metrics_meta()["prefix"]["shared_bytes"] == 0
        held = len(s.store.pages)
        assert held == 2 * len(extents)
        freed = s.prefix.sweep()
        assert freed == held and not s.store.pages
        assert all(e.page.freed and e.carry.freed for e in extents)
        meta = s.eng.metrics_meta()["prefix"]
        assert meta["carry_bytes"] == 0 and meta["extents"] == 0


def test_an_unreferenced_snapshot_goes_down_a_tier_and_is_restored_from_there(
        tiny):
    """The watermark sweep counts snapshots as it counts pages: with a
    small HOT the dead extents' pages and snapshots are demoted, and an
    adopter reads the snapshot back from wherever it lies."""
    cfg, _ = tiny
    first, second = prompts_behind(cfg, 6, 3, (2, 3))
    want = cache_off(tiny, [second])
    with Stack(tiny, hot=6, warm=64) as s:
        s.run([first], new=9)
        assert any(e.carry.tier != Tier.HOT for e in s.prefix.extents())
        got = s.run([second])
    assert got["t0"].prefix_tokens_reused == 3 * P
    np.testing.assert_allclose(logits_of(got), logits_of(want),
                               atol=ROUNDING)


def test_a_snapshot_is_a_copy_the_programs_go_on_donating_the_carry(tiny):
    """The bytes an extent keeps are the publisher's carry at that boundary
    and stay so while the publisher and adopters run on."""
    cfg, _ = tiny
    first, second = prompts_behind(cfg, 7, 2, (6, 2))
    with Stack(tiny) as s:
        s.run([first], new=6)
        chain = s.prefix.match(first[:2 * P])[0]
        kept = [np.array(s.store.read_page(e.carry), copy=True)
                for e in chain]
        assert all(k.any() for k in kept)
        s.run([second], new=6)
        again = [np.array(s.store.read_page(e.carry), copy=True)
                 for e in chain]
    for a, b in zip(kept, again):
        assert np.array_equal(a, b)
    # the boundary after two pages, by the convolution's definition: the
    # session's carry after a prefill of exactly those pages
    with Stack(tiny, share=False) as s:
        s.eng.submit(Request("x", first[:2 * P] + [1] * P, 1))
        s.eng._tick()
        s.eng._tick()               # two chunks in, a third page to go
        assert s.eng.active[0].pos == 2 * P
        (carry,) = s.eng.active[0].carry
    assert np.array_equal(np.asarray(carry).view(np.uint8).reshape(-1),
                          kept[1])


# -- a family whose snapshot does not fit a slot still raises ------------------------


def test_the_delta_rule_family_still_raises_with_both_sizes():
    from oncilla_tpu.models import kda_latent as kl

    cfg = kl.KdaLatentConfig.tiny()
    params = kl.init_params(jax.random.key(0), cfg)
    pb = ServingEngine.page_nbytes(cfg, P)
    snapshot = sum(int(np.prod(shape)) * 4
                   for shape, _ in kl.PAGED_FAMILY.carry_leaves(cfg, 1))
    assert snapshot > pb
    with pytest.raises(ValueError) as err:
        with Stack((cfg, params)):
            pass
    assert "carry" in str(err.value)
    assert f"{snapshot} B" in str(err.value) and f"{pb} B" in str(err.value)
    # with the cache off it serves as it did
    with Stack((cfg, params), share=False) as s:
        assert len(s.run([[1, 2, 3, 4, 5, 6]], new=2)["t0"].out_tokens) == 2


def test_families_without_a_carry_keep_no_snapshot():
    from oncilla_tpu.models import LlamaConfig, init_params_host

    cfg = LlamaConfig.tiny()
    tiny = (cfg, init_params_host(0, cfg))
    prompts = prompts_behind(cfg, 8, 2, (3, 5))
    with Stack(tiny) as s:
        s.run(prompts[:1])
        s.run(prompts[1:])
        extents = s.prefix.extents()
        meta = s.eng.metrics_meta()["prefix"]
        assert extents and all(e.carry is None for e in extents)
        assert all(e.nbytes == e.page.nbytes for e in extents)
        assert len(s.store.pages) == len(extents)
    assert meta["carry_snapshots"] == meta["carry_restores"] == 0
    assert meta["carry_bytes"] == 0 and meta["adoptions"] == 1


# -- spans and counters ------------------------------------------------------------------


def test_one_span_a_snapshot_and_one_a_restore(tiny):
    cfg, _ = tiny
    prompts = prompts_behind(cfg, 10, 2, (3, 6, 2))

    def spans():
        snap = GLOBAL_TRACER.snapshot()
        return {op: snap.get(op, {"count": 0})["count"]
                for op in ("prefix.snapshot", "prefix.restore")}

    with Stack(tiny) as s:
        s.run(prompts[:1])
        before, meta0 = spans(), s.eng.metrics_meta()["prefix"]
        s.run(prompts[1:])
        after, meta1 = spans(), s.eng.metrics_meta()["prefix"]
    taken = meta1["carry_snapshots"] - meta0["carry_snapshots"]
    restored = meta1["carry_restores"] - meta0["carry_restores"]
    assert taken > 0 and restored == 2
    assert after["prefix.snapshot"] - before["prefix.snapshot"] == taken
    assert after["prefix.restore"] - before["prefix.restore"] == restored
    assert meta1["carry_bytes"] > meta0["carry_bytes"] > 0


# -- persisted extents come back with their carry or not at all ----------------------


def test_persisted_extents_come_back_with_their_carry(tiny, tmp_path):
    from oncilla_tpu.persist import FrozenStore

    cfg, _ = tiny
    first, second = prompts_behind(cfg, 12, 3, (2, 5))
    want = cache_off(tiny, [second])
    with Stack(tiny, frozen=FrozenStore(str(tmp_path))) as s:
        s.run([first])
        published = {e.key: np.array(s.store.read_page(e.carry), copy=True)
                     for e in s.prefix.extents()}
    assert published
    frozen = FrozenStore(str(tmp_path))
    metas = [frozen.meta(k) for k in frozen.keys() if k.startswith("prefix-")]
    assert len(metas) == len(published)
    assert all(m["carry_nbytes"] == 4 * 2 * cfg.hidden_size * 4
               for m in metas)
    with Stack(tiny, frozen=frozen) as s:
        back = {e.key: e for e in s.prefix.extents()}
        assert set(back) == set(published)
        for key, ext in back.items():
            assert ext.carry is not None and ext.carry.shared
            assert np.array_equal(s.store.read_page(ext.carry),
                                  published[key])
        assert s.eng.metrics_meta()["prefix"]["carry_bytes"] == sum(
            e.carry.nbytes for e in back.values())
        got = s.run([second])
    assert got["t0"].prefix_tokens_reused == 3 * P
    np.testing.assert_allclose(logits_of(got), logits_of(want),
                               atol=ROUNDING)


def test_an_extent_persisted_without_its_carry_is_not_restored(tiny, tmp_path):
    """A page never comes back without its carry: a record that holds no
    snapshot (or one of another size) is left out with everything below
    it; and a family without a carry takes no record that holds one."""
    from oncilla_tpu.models import LlamaConfig, init_params_host
    from oncilla_tpu.persist import FrozenStore

    cfg, _ = tiny
    prompt, = prompts_behind(cfg, 13, 3, (2,))
    with Stack(tiny, frozen=FrozenStore(str(tmp_path))) as s:
        s.run([prompt])
        chain = s.prefix.match(prompt[:3 * P])[0]
        keys = [e.key for e in chain]
    frozen = FrozenStore(str(tmp_path))
    # strip the snapshot off the chain's second page, as a writer without
    # one would have left it
    fkey = f"prefix-{keys[1]}"
    meta = frozen.meta(fkey)
    data = frozen.read_bytes(fkey)
    frozen.write(fkey, data[:meta["nbytes"]],
                 meta={**meta, "carry_nbytes": 0})
    with Stack(tiny, frozen=frozen) as s:
        back = {e.key for e in s.prefix.extents()}
        assert keys[0] in back
        assert keys[1] not in back and keys[2] not in back   # and below it
        assert all(e.carry is not None for e in s.prefix.extents())
        got = s.run([prompt])
        assert got["t0"].prefix_tokens_reused == P
    # the dense family, over the records of a family with a carry: none
    dense = LlamaConfig.tiny()
    pb = ServingEngine.page_nbytes(dense, P)
    if pb == ServingEngine.page_nbytes(cfg, P):
        with Stack((dense, init_params_host(0, dense)),
                   frozen=FrozenStore(str(tmp_path))) as s:
            assert not s.prefix.extents()
