"""The window- and full-attention family with per-head gates and a share of
its experts (``models/swa_moe.py``) against its plain reference
(``benchmark/references/swa_gqa_moe.py``, which shares no code with it), at
a tiny size on the CPU in float32: the layers unpaged, the chip's share of
an expert layer, and prefill then decode through ``ServingEngine`` with a
page of two kinds, across the window's edge, the window kind's pages
dropped as they leave the window."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import latent_moe as lm
from oncilla_tpu.models import swa_moe as sm
from oncilla_tpu.models.kv_paging import PageKind
from oncilla_tpu.utils.debug import GLOBAL_TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmark", "references", "swa_gqa_moe.py")
P = 4   # page tokens: the tiny window of 10 positions is 2.5 pages


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_swa_gqa_moe", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded(cfg, seed=3):
    """Weights with the selection bias given values, so that it is on the
    tested path."""
    params = sm.init_params(jax.random.key(seed), cfg)
    params["e_bias"] = 0.1 * jax.random.normal(
        jax.random.key(seed + 2), params["e_bias"].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = sm.SwaMoeConfig.tiny()
    return cfg, seeded(cfg), cfg.to_published(), load_reference()


def test_published_round_trip_and_the_layer_kinds():
    cfg = sm.SwaMoeConfig.tiny()
    conf = cfg.to_published()
    assert conf["torch_dtype"] == "float32" and "dtype" not in conf
    assert conf["rope_parameters"]["full_attention"]["factor"] == 4.0
    assert sm.SwaMoeConfig.from_published(conf) == cfg
    assert cfg.full_layers == (0, 4) and cfg.window_layers == (1, 2, 3)
    assert cfg.first_k_dense_replace == 1 and cfg.n_expert_layers == 4
    full = sm.SwaMoeConfig()
    assert len(full.full_layers) == 12 and len(full.window_layers) == 36
    assert full.experts_held == (0, 256) and full.window_pages(16) == 32
    # a cut in depth reads the head of the published lists
    cut = sm.SwaMoeConfig.from_published(
        {**full.to_published(), "num_hidden_layers": 5, "num_experts": 64,
         "vocab_size": 25088})
    assert cut.layer_types == full.layer_types[:5]
    assert cut.num_attention_heads_per_layer == (48, 72, 72, 72, 48)
    assert cut.experts_held == (0, 64) and cut.n_routed_experts == 256
    assert sm.PAGED_FAMILY.page_kinds(cut) == (
        PageKind(2, None, 2), PageKind(3, 512, 2))
    with pytest.raises(ValueError, match="entries for 5 layers"):
        dataclasses.replace(full, num_hidden_layers=5)
    with pytest.raises(ValueError, match="a full and a window layer"):
        sm.SwaMoeConfig.tiny(
            layer_types=(sm.FULL,) * 5,
            num_attention_heads_per_layer=(4,) * 5)
    with pytest.raises(ValueError, match="one head count"):
        sm.SwaMoeConfig.tiny(num_attention_heads_per_layer=(4, 6, 6, 8, 4))


def test_the_layers_unpaged_match_reference_and_choose_its_experts(tiny):
    cfg, params, conf, ref = tiny
    toks = np.random.default_rng(0).integers(1, cfg.vocab, (2, 37)).astype(
        np.int32)
    out, routing = jax.jit(lambda p, t: sm.forward(
        p, t, cfg, return_routing=True))(params, toks)
    want = ref.logits_at(params, toks, np.arange(37), conf)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    assert np.array_equal(np.sort(np.asarray(routing), axis=-1),
                          ref.experts_at(params, toks, conf))


def test_a_window_layer_forgets_and_a_full_layer_does_not(tiny):
    """One attention layer of each kind alone: a key ``sliding_window``
    or more positions back moves a window layer's output nowhere and a full
    layer's everywhere after it."""
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((1, 24, cfg.hidden_size)),
                    jnp.float32)
    moved = h.at[0, 3].add(1.0)
    for kind, pre, reach in ((sm.WINDOW, "w_", cfg.sliding_window),
                             (sm.FULL, "f_", 24)):
        lp = {n: params[pre + n][0] for n in ref.ATTN_LEAVES}
        a, b = (np.asarray(ref.attention_layer(x, lp, conf, kind))[0]
                for x in (h, moved))
        differs = np.abs(a - b).max(axis=-1) > 1e-6
        assert not differs[:3].any() and differs[3:3 + reach].all()
        assert not differs[3 + reach:].any()


def test_rotary_numbers_are_the_references(tiny):
    cfg, _, conf, ref = tiny
    for full, kind in ((True, sm.FULL), (False, sm.WINDOW)):
        inv_freq, factor = sm.rope_of(cfg, full)
        width, want_factor, freqs = ref.rope_of(conf, kind)
        assert 2 * len(inv_freq) == width and factor == want_factor
        np.testing.assert_allclose(inv_freq, freqs, rtol=1e-6)
    # YaRN's ramp is on the tested path: neither plain nor all divided
    plain = 100.0 ** -(np.arange(0, 8, 2) / 8)
    ratio = sm.rope_of(cfg, True)[0] / plain
    assert ratio[0] == 1.0 and abs(ratio[-1] - 0.25) < 1e-6
    assert 0.3 < ratio[1] < 0.9
    # the published numbers: half a head rotated, cos and sin scaled
    big = sm.SwaMoeConfig()
    inv_freq, factor = sm.rope_of(big, True)
    assert inv_freq.shape == (32,) and abs(factor - 1.4852) < 1e-4
    assert sm.rope_of(big, False)[0].shape == (64,)
    np.testing.assert_allclose(
        inv_freq, ref.rope_of(big.to_published(), sm.FULL)[2], rtol=1e-6)


@pytest.mark.parametrize("lengths", [(37, 23), (37, 512), (300, 511)])
def test_reference_lengths_of_one_block_share_their_executables(
        tiny, lengths):
    """The reference runs a sequence at its length rounded up to SEQ_BLOCK
    (what follows a position changes nothing before it): two requests of
    unlike length inside one block build nothing new."""
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(sum(lengths))
    first, second = (rng.integers(1, cfg.vocab, (1, n)).astype(np.int32)
                     for n in lengths)
    want = ref.logits_at(params, first, np.arange(5, lengths[0]), conf)
    built, watching = [], [True]
    # JAX has no way to take one listener off again: this one outlives the
    # test and counts what is built while it is watched, nothing after.
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _s, **kw: built.append(name)
        if watching[0]
        and name == "/jax/core/compile/backend_compile_duration" else None)
    ref.logits_at(params, second, np.arange(3, lengths[1]), conf)
    watching[0] = False
    assert not built
    padded = np.pad(first, ((0, 0), (0, 7)), constant_values=9)
    again = ref.logits_at(params, padded, np.arange(5, lengths[0]), conf)
    np.testing.assert_allclose(again, want, atol=1e-5)


def share_of(cfg, params, first, count):
    """The chip that holds experts [first, first + count) of every layer."""
    cut = dataclasses.replace(cfg, num_experts=count, first_expert=first)
    sliced = dict(params)
    for name in ("w_gate_e", "w_up_e", "w_down_e"):
        sliced[name] = params[name][:, first:first + count]
    return cut, sliced


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        tiny):
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(5)
    T, j = 24, 2
    h = jnp.asarray(rng.standard_normal((T, cfg.hidden_size)), jnp.float32)
    real = jnp.ones((T,), bool)
    ep = {k: params[k][j] for k in ref.ROUTER_LEAVES + ref.EXPERT_LEAVES
          + ref.SHARED_LEAVES}
    whole, ids = ref.expert_layer(h[None], ep, conf)
    shared = np.asarray(ref._swiglu(h[None], ep["ws_gate"], ep["ws_up"],
                                    ep["ws_down"]))[0]
    total, touched = np.zeros((T, cfg.hidden_size), np.float32), 0
    for first in range(0, 16, 4):
        cut, sliced = share_of(cfg, params, first, 4)
        y, n_hit, idx = lm.expert_ffn(h, sliced, j, real, cut)
        # every share routes over all 16 and makes the same choice
        assert np.array_equal(np.sort(np.asarray(idx), -1), np.asarray(ids[0]))
        held = np.asarray(idx)
        assert int(n_hit) == len(np.unique(
            held[(held >= first) & (held < first + 4)]))
        touched += int(n_hit)
        # the reference given the same share gives the same part
        part, _ = ref.expert_layer(
            h[None], {**ep, **{k: sliced[k][j] for k in ref.EXPERT_LEAVES}},
            {**conf, "first_expert": first})
        np.testing.assert_allclose(np.asarray(y), np.asarray(part[0]),
                                   atol=1e-5)
        total += np.asarray(y) - shared
    np.testing.assert_allclose(total + shared, np.asarray(whole[0]),
                               atol=1e-5)
    assert touched == len(np.unique(np.asarray(ids)))


def test_the_reference_imports_nothing_from_the_program():
    with open(REFERENCE) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "oncilla" in line]
    assert "oncilla_tpu" not in source.split('"""', 2)[2]


# -- through ServingEngine ---------------------------------------------------


def serve(cfg, params, prompts, new_tokens, *, hot=256, warm=4, share=False,
          max_active=4, max_batch=None, watch=None, prefetch=0):
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.engine import Request, ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.prefix import PrefixCache
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = ServingEngine.page_nbytes(cfg, P)
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, pb, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("swa"))
    try:
        eng = ServingEngine(params, cfg, store,
                            PrefixCache(store, P) if share else None,
                            page_tokens=P, max_active=max_active,
                            max_batch=max_batch, prefetch_workers=prefetch,
                            name="swa", keep_logits=True)
    except BaseException:
        store.close()
        ctx.tini()
        raise
    try:
        if watch is not None:
            watch(eng)
        for i, (p, n) in enumerate(zip(prompts, new_tokens)):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=n))
        results = {r.tenant: r for r in eng.run()}
        meta = eng.metrics_meta()
        assert not store.pages, "a finished session left pages behind"
    finally:
        eng.close()
        store.close()
        ctx.tini()
    return results, meta


def held_to_reference(results, prompts, params, conf, ref, atol=1e-4):
    for i, prompt in enumerate(prompts):
        res = results[f"t{i}"]
        out = res.out_tokens
        got = np.stack(res.out_logits)
        assert (got.argmax(-1) == out).all()
        seq = np.asarray([list(prompt) + out[:-1]], np.int32)
        rows = np.arange(len(prompt) - 1, seq.shape[1])
        want = ref.logits_at(params, seq, rows, conf)[0]
        np.testing.assert_allclose(got, want, atol=atol)


# (prompt lengths, new tokens, max_active, max_batch). The window is 10
# positions, 2.5 pages of 4: a context past 18 positions is past the window
# and two pages. Sessions of unlike length in one batch, prompts that end
# on a page and not, a prompt under a page, a batch that pads, more
# sessions than seats, decode that crosses the window's edge on its own.
SCHEDULES = {
    "one-session-past-the-window": ((23,), (9,), 1, 1),
    "unlike-lengths": ((37, 6, 21), (7, 19, 9), 3, 4),
    "decode-crosses-the-edge": ((5, 9), (22, 17), 2, 2),
    "seats-change-hands": ((26, 6, 19, 2, 33), (9, 14, 7, 16, 5), 5, 2),
    "whole-pages": ((20, 28), (6, 6), 2, 2),
}


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_engine_prefill_then_decode_matches_the_reference(tiny, name):
    cfg, params, conf, ref = tiny
    lens, new, max_active, max_batch = SCHEDULES[name]
    rng = np.random.default_rng(len(name))
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    results, meta = serve(cfg, params, prompts, new, max_active=max_active,
                          max_batch=max_batch)
    assert all(len(results[f"t{i}"].out_tokens) == n
               for i, n in enumerate(new))
    held_to_reference(results, prompts, params, conf, ref)
    # every whole page of prompt through a page program, up to the family's
    # chunk_pages of one prompt a program
    K = sm.PAGED_FAMILY.chunk_pages
    assert meta["prefill"]["pages"] == sum(n // P for n in lens)
    assert meta["batch"]["prefill_chunks"] == sum(
        -(-(n // P) // K) for n in lens)
    # every page boundary shipped one page a kind; a window-kind page goes
    # once its last key is `window` positions behind the next query
    ends = [n + m - 1 for n, m in zip(lens, new)]       # positions consumed
    window = meta["window"]
    assert window["pages_shipped"] == sum(e // P for e in ends)
    assert window["pages_dropped"] == sum(
        max((e // P * P - cfg.sliding_window) // P, 0) for e in ends) > 0
    kv = meta["kv"]
    assert 0 < kv["positions_held"] < kv["positions_whole"]
    moe = meta["moe"]
    k, Le = cfg.num_experts_per_tok, cfg.n_expert_layers
    assert moe["step_assignments"] == meta["batch"]["size_sum"] * k * Le
    assert 0 < moe["step_expert_rows"] <= moe["step_assignments"]
    assert moe["page_count"] == meta["batch"]["prefill_chunks"]


@pytest.mark.parametrize("window", [8, 12, 5])
def test_other_windows_whole_pages_and_under_two(tiny, window):
    """A window of whole pages (8, 12) and one barely over a page (5)."""
    cfg, params, _, ref = tiny
    cfg = dataclasses.replace(cfg, sliding_window=window)
    rng = np.random.default_rng(window)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (27, 9)]
    seen = []

    def watch(eng):
        drop = eng._drop_passed

        def dropped(sess):
            drop(sess)
            seen.append(sum(e.kind == 1 for e in sess.entries))

        eng._drop_passed = dropped

    results, meta = serve(cfg, params, prompts, (8, 15), max_active=2,
                          max_batch=2, watch=watch)
    held_to_reference(results, prompts, params, cfg.to_published(), ref)
    assert max(seen) == cfg.window_pages(P) == -(-window // P)


def test_a_session_lists_a_windows_worth_of_window_pages_and_the_store_shrinks(
        tiny):
    """At every drop ``store.pages`` loses exactly the dropped page, the
    pool row it held is free again, and no session ever lists more than
    ``window / P + 1`` pages of the window kind; the full kind keeps every
    page."""
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(7)
    lens, new = (30, 11, 21), (12, 20, 8)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    drops, seen = [], {}

    def watch(eng):
        drop, tick = eng._drop_passed, eng._tick
        free_page = eng.store.free_page

        def dropped(sess):
            before = dict(eng.store.pages)
            listed = [e for e in sess.entries if e.kind == 1]
            full = [e for e in sess.entries if e.kind == 0]
            rows = dict(eng._pool_slots[1])
            drop(sess)
            gone = [pid for pid in before if pid not in eng.store.pages]
            left = [e for e in sess.entries if e.kind == 1]
            assert gone == [e.page.page_id for e in listed[:len(gone)]]
            assert left == listed[len(gone):]
            assert [e for e in sess.entries if e.kind == 0] == full
            assert len(eng.store.pages) == len(before) - len(gone)
            for e in listed[:len(gone)]:
                assert e.page.freed and e.arrays is None
                key = (e.page.page_id, e.version)
                assert key not in eng._pool_slots[1]
                if key in rows:
                    assert rows[key] in eng._pool_free[1]
            # a drop follows the ships of one chunk or one step: no more
            # pages leave than were shipped since the session's last drop
            shipped = sess.pos // P - seen.get(sess.req.tenant, 0)
            seen[sess.req.tenant] = sess.pos // P
            assert len(gone) <= shipped
            drops.append((sess.req.tenant, len(gone), len(left), sess.pos))

        def checked_tick():
            tick()
            most = cfg.sliding_window // P + 1
            for sess in eng.active:
                kinds = [e.kind for e in sess.entries]
                assert kinds.count(1) <= most
                assert kinds.count(0) == sess.pos // P
                assert kinds.count(1) + sess.dropped[1] == sess.pos // P
                assert sess.dropped[0] == 0

        def no_demote(page):
            assert page.tier.value == "hbm"
            free_page(page)

        eng._drop_passed, eng._tick = dropped, checked_tick
        eng.store.free_page = no_demote

    results, meta = serve(cfg, params, prompts, new, max_active=3,
                          max_batch=4, watch=watch)
    held_to_reference(results, prompts, params, conf, ref)
    assert sum(n for _, n, _, _ in drops) == meta["window"]["pages_dropped"]
    assert meta["window"]["pages_dropped"] > 0
    # after a drop a session holds ceil(window / P) window-kind pages at most
    assert max(left for _, _, left, _ in drops) == cfg.window_pages(P) == 3
    assert meta["moves"]["promote"] == meta["moves"]["demote"] == 0


def test_a_finished_session_frees_both_kinds(tiny):
    cfg, params, _, _ = tiny
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (22, 13)]
    freed = []

    def watch(eng):
        finish, tick = eng._finish, eng._tick

        def finished(sess, abandon=False):
            held = [(e.kind, e.page) for e in sess.entries]
            assert {k for k, _ in held} == {0, 1}
            finish(sess, abandon)
            # its pages wait for the tick's one free_pages call
            assert not sess.entries and [p for _, p in held] == eng._ended[
                -len(held):]
            freed.append(held)

        def checked_tick():
            tick()
            assert not eng._ended
            assert all(page.freed for held in freed for _, page in held)

        eng._finish, eng._tick = finished, checked_tick

    serve(cfg, params, prompts, (6, 9), max_active=2, max_batch=2,
          watch=watch)     # serve() asserts the store is empty at the end
    assert len(freed) == 2


def test_both_kinds_move_through_the_tiers_alike(tiny):
    """A HOT tier under the working set: pages of both kinds are demoted
    and promoted (through the prefetcher too) and the logits still hold; a
    dropped page is freed from whatever tier it lies in."""
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(9)
    lens, new = (26, 14, 19, 23), (8, 12, 6, 9)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    moved, dropped_from = set(), set()

    def watch(eng):
        move, free_pages = eng.store._move, eng.store.free_pages
        # The kinds' pages differ in size: a page's size says its kind.
        sizes = [4 * int(np.prod(shape)) for shape in eng.page_shapes]
        assert len(set(sizes)) == 2
        ship, place_chunk = eng._ship, eng._place_chunk

        def note(pages):
            assert [p.nbytes for p in pages] == sizes

        def shipped(sess):
            ship(sess)
            note([e.page for e in sess.entries[-2:]])

        def placed_chunk(made, pages):
            placed = place_chunk(made, pages)
            for kinds in placed:
                note(kinds)
            return placed

        def moving(page, to, data=None):
            moved.add((sizes.index(page.nbytes), to.value))
            move(page, to, data=data)

        def freeing(pages):
            for page in pages:
                if sizes.index(page.nbytes) == 1 and eng.active:
                    dropped_from.add(page.tier.value)
            free_pages(pages)

        eng._ship, eng.store._move = shipped, moving
        eng._place_chunk = placed_chunk
        eng.store.free_pages = freeing

    results, meta = serve(cfg, params, prompts, new, hot=6, warm=8,
                          max_active=4, max_batch=2, watch=watch, prefetch=2)
    held_to_reference(results, prompts, params, conf, ref)
    assert {(0, "host"), (1, "host"), (0, "hbm"), (1, "hbm")} <= moved
    assert meta["moves"]["promote"] > 0 and meta["degraded"] == {
        "capacity_free": 0, "pressure": 0}
    assert dropped_from - {"hbm"}, "no window page was dropped below HOT"


def test_the_store_takes_both_sizes_and_refuses_a_larger_one(tiny):
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.engine import ServingEngine
    from oncilla_tpu.serving.tiers import Tier, TieredPageStore

    cfg = tiny[0]
    pb = ServingEngine.page_nbytes(cfg, P)
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    small, large = 2 * 2 * KV * P * hd * 4, 3 * 2 * KV * P * hd * 4
    assert pb == large      # the store is built for the larger kind
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, pb, hot_capacity=8, warm_capacity=8)
    try:
        rng = np.random.default_rng(0)
        a = rng.integers(0, 256, small, dtype=np.uint8)
        b = rng.integers(0, 256, large, dtype=np.uint8)
        pa, pb_ = store.alloc_page(a), store.alloc_page(b)
        assert (pa.nbytes, pb_.nbytes) == (small, large)
        assert store.occupancy()["hbm"]["bytes"] == small + large
        for page, want in ((pa, a), (pb_, b)):
            assert np.array_equal(np.asarray(store.read_page(page)), want)
        # every tier and back: each page keeps its own size
        for to in (Tier.WARM, Tier.COLD):
            store.demote(pa, to)
            store.demote(pb_, to)
            assert np.array_equal(np.asarray(store.read_page(pa)), a)
            assert np.array_equal(np.asarray(store.read_page(pb_)), b)
        buf = np.empty(pb, np.uint8)
        assert store.fetch_bytes(pa, buf) == (pa.version, True)
        assert np.array_equal(buf[:small], a)
        store.promote(pa)
        assert pa.tier == Tier.HOT and pa.nbytes == small
        assert np.array_equal(np.asarray(store.read_page(pa)), a)
        clone = store.cow(pa)
        assert clone.nbytes == small
        assert np.array_equal(np.asarray(store.read_page(clone)), a)
        store.write_page(pa, a[::-1].copy())
        with pytest.raises(ValueError, match="page write"):
            store.write_page(pa, b)
        # a rewrite takes the page where it lies too, in HOT and below
        for page in (pa, store.alloc_page(a, prefer=Tier.WARM)):
            store.write_page(page, jnp.asarray(a))
            assert np.array_equal(np.asarray(store.read_page(page)), a)
            with pytest.raises(ValueError, match="page write"):
                store.write_page(page, jnp.asarray(b))
        # a page handed over where it lies, on the device: HOT takes it
        # device to device, a tier below gets it pulled
        dev = store.alloc_page(jnp.asarray(b))
        assert dev.tier == Tier.HOT and dev.nbytes == large
        assert np.array_equal(np.asarray(store.read_page(dev)), b)
        low = store.alloc_page(jnp.asarray(a), prefer=Tier.WARM)
        assert low.tier == Tier.WARM and low.nbytes == small
        assert np.array_equal(np.asarray(store.read_page(low)), a)
        with pytest.raises(ValueError, match="at most"):
            store.alloc_page(jnp.zeros(large + 4, jnp.uint8))
        with pytest.raises(ValueError, match="at most"):
            store.alloc_page(np.zeros(large + 4, np.uint8))
        with pytest.raises(ValueError, match="at most"):
            store.alloc_page(np.zeros(0, np.uint8))
    finally:
        store.close()
        ctx.tini()


def test_prefix_cache_with_kinds_raises(tiny):
    cfg, params, _, _ = tiny
    with pytest.raises(ValueError, match="comes in kinds"):
        serve(cfg, params, [[1, 2, 3]], (2,), share=True)


def test_the_share_of_the_experts_is_served_as_the_reference_computes_it(tiny):
    """A chip that holds experts 4..11 of 16, through the engine, against
    the reference given the same share: the partial sum goes on."""
    cfg, params, conf, ref = tiny
    cut, sliced = share_of(cfg, params, 4, 8)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (21, 5)]
    results, meta = serve(cut, sliced, prompts, (6, 16), max_active=2,
                          max_batch=2)
    held_to_reference(results, prompts, sliced, cut.to_published(), ref)
    whole, _ = serve(cfg, params, prompts, (6, 16), max_active=2, max_batch=2)
    assert not np.allclose(np.stack(results["t0"].out_logits),
                           np.stack(whole["t0"].out_logits), atol=1e-3)
    # held experts only are counted
    assert (meta["moe"]["step_expert_rows"]
            <= meta["batch"]["steps"] * 8 * cut.n_expert_layers)


def test_the_drop_has_a_span_under_the_chunk_and_under_the_step(tiny):
    """``prefill.drop`` and ``step.drop`` run inside ``serve_prefill_chunk``
    and ``serve_batch_step`` (so the tick's unattributed share stays
    honest), once a chunk's ships or a step's ship of a family with a
    window kind; a family without one opens neither."""
    cfg, params, _, _ = tiny
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (22, 7)]
    stack, inside = [], {}
    span = GLOBAL_TRACER.span

    class Watched:
        def __init__(self, name, **kw):
            self.name, self.inner = name, span(name, **kw)

        def __enter__(self):
            inside.setdefault(self.name, set()).update(stack)
            stack.append(self.name)
            return self.inner.__enter__()

        def __exit__(self, *exc):
            stack.pop()
            return self.inner.__exit__(*exc)

    GLOBAL_TRACER.span = Watched
    try:
        before = {k: v["count"] for k, v in GLOBAL_TRACER.snapshot().items()}
        _, meta = serve(cfg, params, prompts, (9, 14), max_active=2,
                        max_batch=2)
        after = {k: v["count"] for k, v in GLOBAL_TRACER.snapshot().items()}
    finally:
        GLOBAL_TRACER.span = span
    assert "serve_prefill_chunk" in inside["prefill.drop"]
    assert {"serve_batch_step", "step.scatter"} <= inside["step.drop"]
    assert "step.ship" not in inside["step.drop"]
    count = {k: after[k] - before.get(k, 0) for k in after}
    # once after a chunk's ships (a ship a page), once after a step's ship
    assert count["prefill.drop"] == meta["batch"]["prefill_chunks"]
    assert count["prefill.ship"] == meta["prefill"]["pages"] == 22 // P + 1
    assert count["step.drop"] == count["step.ship"]
    assert (count["prefill.ship"] + count["step.ship"]
            == meta["window"]["pages_shipped"])


def test_the_join_is_the_engines_own_concatenation_in_one_dispatch(tiny):
    """``PagedFamily.context`` hands the page program what the engine's
    concatenation would, padded with blank pages (the full kind to a power
    of two, the window kind to a window's worth)."""
    cfg = tiny[0]
    fam = sm.PAGED_FAMILY
    rng = np.random.default_rng(4)

    def page(k):
        shapes = fam.leaf_shapes(cfg, P)[fam.kind_leaves(cfg)[k]]
        return tuple(jnp.asarray(rng.standard_normal(s), jnp.float32)
                     for s in shapes)

    for n_full, n_window in ((0, 0), (1, 1), (3, 3), (5, 2), (8, 3)):
        full = [page(0) for _ in range(n_full)]
        window = [page(1) for _ in range(n_window)]
        ctx = fam.context([full, window], cfg, P)
        to = 1 << (n_full - 1).bit_length() if n_full else 0
        assert [a.shape[3] for a in ctx] == [to * P] * 2 + [
            cfg.window_pages(P) * P] * 2
        for i, (pages, at) in enumerate(((full, 0), (full, 1), (window, 0),
                                         (window, 1))):
            got = np.asarray(ctx[i])
            want = (np.concatenate([np.asarray(p[at]) for p in pages], axis=3)
                    if pages else got[:, :, :, :0])
            assert np.array_equal(got[:, :, :, :want.shape[3]], want)
            assert not got[:, :, :, want.shape[3]:].any()
    for other in (lm.PAGED_FAMILY,):
        assert other.context is None


def test_a_kinds_block_table_is_the_bucket_of_its_longest_seat(tiny):
    """As for every family: a kind's table is as wide as the power of two
    over the most pages a seat lists of it, so the window kind's stops
    growing at a window's worth while the full kind's follows the
    context."""
    cfg, params, _, _ = tiny
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (22, 7)]
    seen = []

    def watch(eng):
        batch_pool = eng._batch_pool

        def pooled(batch):
            pools, tables, keys = batch_pool(batch)
            seen.append(([t.shape[1] for t in tables],
                         [max(len(mine) for mine in kind) for kind in keys]))
            return pools, tables, keys

        eng._batch_pool = pooled

    serve(cfg, params, prompts, (9, 30), max_active=2, max_batch=2,
          watch=watch)
    for widths, most in seen:
        assert widths == [1 << (n - 1).bit_length() if n else 0
                          for n in most]
    most_window = cfg.window_pages(P)
    assert max(w[1] for w, _ in seen) == 1 << (most_window - 1).bit_length()
    assert max(w[0] for w, _ in seen) > max(w[1] for w, _ in seen)


def test_the_programs_name_their_mechanisms(tiny):
    """``attn_full``, ``attn_window``, ``gate`` and ``experts`` are scopes
    of both programs (what a device trace groups their operations by)."""
    cfg, params, _, _ = tiny
    fam = sm.PAGED_FAMILY
    B, N = 2, 4

    def leaves(batch, tokens=P):
        return tuple(jnp.zeros(s, jnp.float32)
                     for s in fam.leaf_shapes(cfg, tokens, batch))

    rows = tuple(jnp.zeros((N, s[0]) + s[2:], jnp.float32)
                 for s in fam.leaf_shapes(cfg, P))
    step = sm.swa_decode_batch_step_jit.lower(
        params, jnp.zeros((B,), jnp.int32), jnp.zeros((B, 6), jnp.int32),
        np.int32(B), rows, (jnp.zeros((B, 2), jnp.int32),) * 2, leaves(B),
        cfg)
    page = sm.swa_decode_page_jit.lower(
        params, jnp.zeros((1, P), jnp.int32), jnp.zeros((3,), jnp.int32),
        leaves(1, 2 * P), leaves(1), cfg)
    for lowered in (step, page):
        text = lowered.as_text(debug_info=True)
        for scope in ("attn_full", "attn_window", "gate", "experts"):
            assert scope in text, scope


def test_a_family_of_one_kind_is_as_it_was():
    """The three families that name no kinds: one kind of every cached
    layer, the leaf shape, page size, meta rows and table they always had."""
    from oncilla_tpu.models import LlamaConfig
    from oncilla_tpu.models import kda_latent as kl
    from oncilla_tpu.serving.engine import DENSE_FAMILY, ServingEngine

    dense = LlamaConfig.tiny()
    latent = lm.LatentMoeConfig.tiny()
    kda = kl.KdaLatentConfig.tiny()
    for fam, cfg, layers, leaves in (
            (DENSE_FAMILY, dense, dense.n_layers, 2),
            (lm.PAGED_FAMILY, latent, latent.n_layers, 1),
            (kl.PAGED_FAMILY, kda, 1, 1)):
        assert fam.kinds is None
        assert fam.page_kinds(cfg) == (PageKind(layers, None, leaves),)
        shape = fam.leaf_shape(cfg, 8, batch=3)
        assert shape[0] == layers and shape[1] == 3 and shape[3] == 8
        assert fam.leaf_shapes(cfg, 8, batch=3) == (shape,) * leaves
        assert ServingEngine.page_nbytes(cfg, 8) == (
            leaves * int(np.prod(fam.leaf_shape(cfg, 8))) * 4)
    assert ServingEngine.page_nbytes(dense, 4) == (
        2 * dense.n_layers * dense.n_kv_heads * 4 * dense.head_dim * 4)
    # the published cuts: 3 MiB and 36 KiB as PERF.md has them
    intern = LlamaConfig(vocab=92544, dim=2048, n_layers=24, n_heads=16,
                         n_kv_heads=8, ffn_hidden=8192)
    assert ServingEngine.page_nbytes(intern, 16) == 3 << 20
    ling = dataclasses.replace(kl.KdaLatentConfig(), num_hidden_layers=7,
                               first_k_dense_replace=1, num_experts=128,
                               vocab_size=39296)
    assert ServingEngine.page_nbytes(ling, 16) == 36 << 10
    # and the new family's: 256 KiB and 384 KiB, the store built for 384
    full = sm.SwaMoeConfig()
    cut = sm.SwaMoeConfig.from_published(
        {**full.to_published(), "num_hidden_layers": 5, "num_experts": 64,
         "vocab_size": 25088})
    assert ServingEngine.page_nbytes(cut, 16) == 384 << 10
    shapes = sm.PAGED_FAMILY.leaf_shapes(cut, 16)
    assert shapes == ((2, 1, 8, 16, 128),) * 2 + ((3, 1, 8, 16, 128),) * 2
    assert 2 * int(np.prod(shapes[0])) * 4 == 256 << 10


def _family_case(name):
    from oncilla_tpu.models import LlamaConfig, init_params
    from oncilla_tpu.models import kda_latent as kl

    if name == "dense":
        cfg = LlamaConfig.tiny()
        return cfg, init_params(jax.random.key(0), cfg)
    mod = {"latent": lm, "kda": kl, "swa": sm}[name]
    cfg = next(c for c in vars(mod).values()
               if isinstance(c, type) and hasattr(c, "tiny")).tiny()
    return cfg, mod.init_params(jax.random.key(0), cfg)


@pytest.mark.parametrize("name", ["dense", "latent", "kda", "swa"])
def test_every_familys_ship_hands_the_store_its_page_on_the_device(name):
    """Whatever the family, a page goes to the store as a uint8 vector
    that lies on the device (HOT takes it device to device), or, a chunk
    of several pages, as rows of one uint8 array there, and a family with
    experts has its chunks' expert counts looked at later, all of them."""
    cfg, params = _family_case(name)
    handed = []

    def watch(eng):
        alloc_page, alloc_pages = eng.store.alloc_page, eng.store.alloc_pages

        def alloc(data, *a, **kw):
            handed.append(data)
            return alloc_page(data, *a, **kw)

        def alloc_many(rows, count, *a, **kw):
            assert isinstance(rows, jax.Array) and rows.ndim == 2
            handed.extend(rows[i] for i in range(count))
            return alloc_pages(rows, count, *a, **kw)

        eng.store.alloc_page, eng.store.alloc_pages = alloc, alloc_many

    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (13, 6)]
    _, meta = serve(cfg, params, prompts, (6, 7), max_active=2, max_batch=2,
                    watch=watch)
    assert handed and all(
        isinstance(d, jax.Array) and d.dtype == jnp.uint8 and d.ndim == 1
        for d in handed)
    kinds = 2 if name == "swa" else 1
    assert len(handed) == kinds * (19 // P + 13 // P)
    if name != "dense":
        assert (meta["moe"]["page_count"]
                == meta["batch"]["prefill_chunks"] > 0)
        assert meta["moe"]["page_expert_rows"] > 0


def test_a_one_kind_engine_passes_what_it_always_passed():
    """The dense family through the engine: the step's meta rows are (B, 4)
    with the last column 0, the page's meta (2,), the table one array."""
    from oncilla_tpu.models import LlamaConfig, init_params
    from oncilla_tpu.serving import engine as eng_mod

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.key(0), cfg)
    seen = {"step": [], "page": []}
    step, page = (eng_mod.paged_decode_batch_step_jit,
                  eng_mod.paged_decode_page_jit)

    def seen_step(params, tokens, meta, pool_k, pool_v, table, *rest):
        seen["step"].append((meta.shape, np.asarray(meta)[:, 3].max(),
                             table.shape, pool_k.shape == pool_v.shape))
        return step(params, tokens, meta, pool_k, pool_v, table, *rest)

    def seen_page(params, tokens, meta, *rest):
        seen["page"].append(np.asarray(meta).tolist())
        return page(params, tokens, meta, *rest)

    eng_mod.paged_decode_batch_step_jit = seen_step
    eng_mod.paged_decode_page_jit = seen_page
    try:
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (9, 14)]
        _, meta = serve(cfg, params, prompts, (5, 4), max_active=2,
                        max_batch=2)
    finally:
        eng_mod.paged_decode_batch_step_jit = step
        eng_mod.paged_decode_page_jit = page
    assert seen["page"] == [[0, 0], [0, 0], [4, 0], [4, 0], [8, 0]]
    assert seen["step"] and all(
        shape[1] == 4 and last == 0 and len(tab) == 2 and same
        for shape, last, tab, same in seen["step"])
    assert meta["window"] == {"pages_shipped": 0, "pages_dropped": 0}
    assert meta["kv"]["positions_held"] == meta["kv"]["positions_whole"] > 0
