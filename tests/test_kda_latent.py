"""The delta-rule / latent-attention / group-limited-expert family
(``models/kda_latent.py``) against its plain reference
(``benchmark/references/kda_latent_moe.py``, which shares no code with it),
at a tiny size on the CPU in float32: the KDA layer's two forms against the
per-token recurrence, the group-limited choice, the chip's share of an
expert layer, and prefill then decode through ``ServingEngine`` with a
carry that follows its session through seats."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import kda_latent as kl
from oncilla_tpu.models import latent_moe as lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = 4   # page tokens


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_kda_latent_moe",
        os.path.join(ROOT, "benchmark", "references", "kda_latent_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded(cfg, seed=3):
    """Weights with every constant leaf given values, so that every term
    of the equations is on the tested path."""
    params = kl.init_params(jax.random.key(seed), cfg)
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed + 2), 4)
    params["e_bias"] = 0.1 * jax.random.normal(k1, params["e_bias"].shape)
    params["kda_A_log"] = 0.3 * jax.random.normal(
        k2, params["kda_A_log"].shape)
    params["kda_dt_bias"] = -1.0 + 0.5 * jax.random.normal(
        k3, params["kda_dt_bias"].shape)
    params["kda_o_norm"] = 1.0 + 0.1 * jax.random.normal(
        k4, params["kda_o_norm"].shape)
    return params


def zero_carry(cfg):
    """An empty carry of one session: the state and the convolution's
    inputs, zeros, as the engine makes it."""
    from oncilla_tpu.serving.engine import _zero_carry

    return _zero_carry(kl.PAGED_FAMILY, cfg, 1)


def forward(params, tokens, cfg, chunk=16):
    """The program's layers over ONE sequence with no pages and no engine:
    logits (S, V) from an empty carry, the KDA layers ``chunk`` tokens at a
    time, the latent layers causally over the whole sequence; and the
    experts chosen, (expert layers, S, k)."""
    S = tokens.shape[0]
    positions = jnp.arange(S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    real = jnp.ones((S,), bool)
    x = params["embed"][tokens].astype(jnp.float32)
    state, conv = (a[:, 0] for a in zero_carry(cfg))
    routing = []
    for i in range(cfg.n_layers):
        if i in cfg.latent_layers:
            def attend(h, m=cfg.latent_layers.index(i)):
                qn, qr, entry = lm.latent_qkv(h, params, m, positions, cfg)
                return lm.attend_expanded(qn, qr, entry, causal, params, m,
                                          cfg)
        else:
            def attend(h, l=cfg.kda_layers.index(i)):
                s, c, ys = state[l], conv[l], []
                for t0 in range(0, S, chunk):
                    y, s, c = kl.kda_chunk(h[t0:t0 + chunk], s, c, params, l,
                                           cfg)
                    ys.append(y)
                return jnp.concatenate(ys, axis=0)
        x, _, idx = kl._block(x, params, i, real, cfg, attend)
        if idx is not None:
            routing.append(idx)
    return kl._logits(params, x, cfg), jnp.stack(routing)


@pytest.fixture(scope="module")
def tiny():
    cfg = kl.KdaLatentConfig.tiny()
    return cfg, seeded(cfg), cfg.to_published(), load_reference()


def test_published_round_trip_and_the_layer_pattern():
    cfg = kl.KdaLatentConfig.tiny()
    conf = cfg.to_published()
    assert conf["torch_dtype"] == "float32" and "dtype" not in conf
    assert kl.KdaLatentConfig.from_published(conf) == cfg
    assert cfg.latent_layers == (2,) and cfg.kda_layers == (0, 1, 3)
    full = kl.KdaLatentConfig()
    assert full.latent_layers == (5, 11, 17, 23, 29, 35, 41)
    assert len(full.kda_layers) == 35 and full.experts_held == (0, 512)
    cut = dataclasses.replace(full, num_hidden_layers=7, num_experts=128)
    assert cut.latent_layers == (5,) and cut.kda_layers == (0, 1, 2, 3, 4, 6)
    assert cut.experts_held == (0, 128) and cut.n_routed_experts == 512
    with pytest.raises(ValueError, match="no latent layer"):
        dataclasses.replace(full, num_hidden_layers=5)


def test_the_layers_unpaged_match_reference_and_choose_its_experts(tiny):
    cfg, params, conf, ref = tiny
    toks = np.random.default_rng(0).integers(1, cfg.vocab, (1, 37)).astype(
        np.int32)
    out, routing = jax.jit(lambda p, t: forward(p, t, cfg, chunk=8))(
        params, toks[0])
    want = ref.logits_at(params, toks, np.arange(37), conf)[0]
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    assert np.array_equal(np.sort(np.asarray(routing), axis=-1),
                          ref.experts_at(params, toks, conf)[:, 0])


@pytest.mark.parametrize("lengths", [(37, 23), (37, 512), (300, 511)])
def test_reference_lengths_of_one_block_share_their_executables(
        tiny, lengths, monkeypatch):
    """The reference runs a sequence at its length rounded up to
    ``SEQ_BLOCK``: a second length of the same block builds no executable
    (the benchmark compares ten requests of ten lengths after every
    window), and what is appended changes no logit before it."""
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(1, cfg.vocab, (1, n)).astype(np.int32)
                     for n in lengths)
    ref.logits_at(params, first, np.arange(3, lengths[0]), conf)
    built = []
    # JAX has no way to take one listener off again: this one outlives the
    # test and counts what is built while it is watched, nothing after.
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _s, **kw: built.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    padded = ref.logits_at(params, second, np.arange(5, lengths[1]), conf)
    assert not built
    monkeypatch.setattr(ref, "SEQ_BLOCK", 1)
    plain = ref.logits_at(params, second, np.arange(5, lengths[1]), conf)
    assert padded.shape == plain.shape == (1, lengths[1] - 5, cfg.vocab)
    np.testing.assert_allclose(padded, plain, atol=1e-5)
    assert ref.experts_at(params, second, conf).shape[2] == lengths[1]


@pytest.mark.parametrize("chunk", [1, 3, 4, 16])
def test_kda_step_form_equals_chunk_form_equals_the_recurrence(tiny, chunk):
    """One KDA layer over one sequence three ways: a token at a time from
    the carry, ``chunk`` tokens at a time from the carry, and the
    reference's plain recurrence over the whole sequence."""
    cfg, params, conf, ref = tiny
    S, layer = 16, 1
    h = jnp.asarray(np.random.default_rng(chunk).standard_normal(
        (S, cfg.hidden_size)), jnp.float32)
    want = np.asarray(ref._kda(
        h[None], {k: params[k][layer] for k in ref.KDA_LEAVES},
        ref.dims_of(conf))[0])
    state, conv = (a[layer] for a in zero_carry(cfg))    # (1, ...)
    ys = []
    for t in range(S):
        y, state, conv = kl.kda_step(h[t:t + 1], state, conv, params, layer,
                                     cfg)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys), want, atol=1e-5)
    s2, c2 = (a[layer, 0] for a in zero_carry(cfg))
    ys = []
    for t0 in range(0, S, chunk):
        y, s2, c2 = kl.kda_chunk(h[t0:t0 + chunk], s2, c2, params, layer,
                                 cfg)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys), want, atol=1e-5)
    # and both leave the same carry behind
    np.testing.assert_allclose(np.asarray(s2), np.asarray(state[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(conv[0]),
                               atol=1e-6)
    assert float(jnp.abs(state).max()) > 1e-3


def test_the_safe_gate_bounds_a_chunks_decay():
    """Sixteen tokens at the lower bound decay by e^-80 and no further:
    every intra-chunk factor is finite in float32."""
    cfg = kl.KdaLatentConfig.tiny()
    params = seeded(cfg)
    params["kda_dt_bias"] = jnp.full_like(params["kda_dt_bias"], 80.0)
    h = jnp.asarray(np.random.default_rng(1).standard_normal(
        (16, cfg.hidden_size)), jnp.float32)
    s, c = (a[0, 0] for a in zero_carry(cfg))
    y, s, c = kl.kda_chunk(h, s + 1.0, c, params, 0, cfg)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(s)).all()
    assert 16 * cfg.kda_lower_bound == -80.0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_choice_equals_the_reference(tiny, seed):
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(seed)
    T = 40
    h = jnp.asarray(rng.standard_normal((T, cfg.hidden_size)), jnp.float32)
    idx, weights, hit = lm.route(h, params, 1, jnp.ones((T,), bool), cfg)
    w_ref, ids = ref._route(h[None], params["w_router"][1],
                            params["e_bias"][1], ref.dims_of(conf))
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.asarray(ids[0]))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(w_ref[0]),
                               atol=1e-6)
    # the limit binds: every row's experts lie in topk_group groups, and
    # without it some row would have chosen from more
    size = cfg.router_experts // cfg.n_group
    groups = [set(r) for r in np.asarray(idx) // size]
    assert max(len(g) for g in groups) <= cfg.topk_group
    free = dataclasses.replace(cfg, n_group=1, topk_group=1)
    idx_free, _, _ = lm.route(h, params, 1, jnp.ones((T,), bool), free)
    assert max(len(set(r)) for r in np.asarray(idx_free) // size) > cfg.topk_group
    assert np.array_equal(np.asarray(hit),
                          np.isin(np.arange(cfg.router_experts), idx))


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


def test_a_row_whose_experts_all_live_elsewhere_gets_the_shared_expert(tiny):
    cfg, params, _, _ = tiny
    herd = dict(params)
    bias = np.zeros(params["e_bias"].shape, np.float32)
    bias[:, 8:] = 50.0      # every choice falls in groups 2 and 3
    herd["e_bias"] = jnp.asarray(bias)
    cut, sliced = share_of(cfg, herd, 0, 4)
    h = jnp.asarray(np.random.default_rng(6).standard_normal(
        (5, cfg.hidden_size)), jnp.float32)
    y, n_hit, idx = lm.expert_ffn(h, sliced, 0, jnp.ones((5,), bool), cut)
    assert int(n_hit) == 0 and np.asarray(idx).min() >= 8
    shared = lm._swiglu(h, sliced["ws_gate"][0], sliced["ws_up"][0],
                        sliced["ws_down"][0], jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(shared), atol=1e-6)


# -- through ServingEngine ---------------------------------------------------


def serve(cfg, params, prompts, new_tokens, *, hot=64, warm=4, share=False,
          max_active=4, max_batch=None, watch=None):
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.engine import Request, ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.prefix import PrefixCache
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = ServingEngine.page_nbytes(cfg, P)
    assert pb == len(cfg.latent_layers) * P * cfg.latent_width * 4
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, pb, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("kda"))
    try:
        eng = ServingEngine(params, cfg, store,
                            PrefixCache(store, P) if share else None,
                            page_tokens=P, max_active=max_active,
                            max_batch=max_batch, prefetch_workers=0,
                            name="kda", keep_logits=True)
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


# (prompt lengths, new tokens, max_active, max_batch): whole pages and not,
# a prompt under a page, a batch that pads (3 of 4 rows), more sessions
# than seats.
SCHEDULES = {
    "one-session": ((11,), (7,), 1, 1),
    "padding-rows": ((9, 3, 14), (6, 9, 5), 3, 4),
    "seats-change-hands": ((13, 6, 9, 2, 17), (9, 4, 7, 6, 5), 5, 2),
    "whole-pages": ((8, 12), (5, 5), 2, 2),
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
    carry, tails = meta["carry"], meta["tails"]
    assert (carry["seats_kept"] + carry["seats_written"]
            == tails["seats_kept"] + tails["seats_written"]
            == meta["batch"]["size_sum"])
    assert carry["seats_written"] >= len(lens)
    assert meta["batch"]["prefill_chunks"] == sum(n // P for n in lens)
    moe = meta["moe"]
    k, Le = cfg.num_experts_per_tok, cfg.n_expert_layers
    assert moe["step_assignments"] == meta["batch"]["size_sum"] * k * Le
    assert 0 < moe["step_expert_rows"] <= moe["step_assignments"]
    assert moe["page_count"] == meta["batch"]["prefill_chunks"]


def test_a_session_keeps_its_carry_when_it_loses_and_regains_its_seat(tiny):
    """Five sessions on two seats: sessions leave their seat alive, sit
    down again later, and still agree with the reference: the carry went
    out with them and came back."""
    cfg, params, conf, ref = tiny
    lens, new, max_active, max_batch = SCHEDULES["seats-change-hands"]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    left_alive, stacks = [], []

    def watch(eng):
        unseat, seat_carries = eng._unseat, eng._seat_carries

        def unseated(sess):
            unseat(sess)
            left_alive.append(sess.req.tenant)
            assert sess.carry is not None and len(sess.carry) == 2
            assert sess.carry[0].shape[1] == 1

        def carried(joined, moved):
            seat_carries(joined, moved)
            assert all(s.carry is None and s.seat is not None for s in joined)
            stacks.append([a.shape for a in eng._carry])

        eng._unseat, eng._seat_carries = unseated, carried

    results, meta = serve(cfg, params, prompts, new, max_active=max_active,
                          max_batch=max_batch, watch=watch)
    assert left_alive, "no session ever lost its seat alive"
    Lk, H, dk = len(cfg.kda_layers), cfg.num_attention_heads, cfg.head_dim
    assert [Lk, 2, H, dk, dk] in [list(s[0]) for s in stacks]
    held_to_reference(results, prompts, params, conf, ref)
    assert meta["carry"]["seats_kept"] > 0
    assert meta["preempts"].get("slot", 0) > 0


def test_the_share_of_the_experts_is_served_as_the_reference_computes_it(tiny):
    """A chip that holds experts 4..11 of 16, through the engine, against
    the reference given the same share: the partial sum goes on."""
    cfg, params, conf, ref = tiny
    cut, sliced = share_of(cfg, params, 4, 8)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (10, 5)]
    results, meta = serve(cut, sliced, prompts, (6, 6), max_active=2,
                          max_batch=2)
    held_to_reference(results, prompts, sliced, cut.to_published(), ref)
    whole, _ = serve(cfg, params, prompts, (6, 6), max_active=2, max_batch=2)
    assert not np.allclose(np.stack(results["t0"].out_logits),
                           np.stack(whole["t0"].out_logits), atol=1e-3)
    # held experts only are counted
    assert (meta["moe"]["step_expert_rows"]
            <= meta["batch"]["steps"] * 8 * cut.n_expert_layers)


def test_prefix_cache_with_a_carry_raises(tiny):
    cfg, params, _, _ = tiny
    with pytest.raises(ValueError, match="carry"):
        serve(cfg, params, [[1, 2, 3]], (2,), share=True)


def test_families_without_a_carry_are_as_they_were():
    from oncilla_tpu.models import LlamaConfig
    from oncilla_tpu.serving.engine import DENSE_FAMILY, family_of

    tiny_cfg = kl.KdaLatentConfig.tiny()
    fam = family_of(tiny_cfg)
    assert fam is kl.PAGED_FAMILY and fam.carry_leaves is not None
    assert fam.leaf_shape(tiny_cfg, 8, batch=3) == (
        1, 3, 1, 8, tiny_cfg.latent_width)
    shapes = [s for s, _ in fam.carry_leaves(tiny_cfg, 5)]
    assert shapes == [(3, 5, 4, 8, 8), (3, 5, 3, 3 * 4 * 8)]
    for other in (DENSE_FAMILY, lm.PAGED_FAMILY):
        assert other.carry_leaves is None and other.cached_layers is None
    dense = LlamaConfig.tiny()
    assert DENSE_FAMILY.leaf_shape(dense, 8)[0] == dense.n_layers
    latent = lm.LatentMoeConfig.tiny()
    assert latent.n_group == 1 and latent.experts_held == (0, 8)
    assert lm.PAGED_FAMILY.leaf_shape(latent, 8)[0] == latent.n_layers
    # the full-size cut: a page of one cached layer, a carry of ~13.5 MB
    cut = dataclasses.replace(kl.KdaLatentConfig(), num_hidden_layers=7,
                              first_k_dense_replace=1, num_experts=128,
                              vocab_size=39296)
    from oncilla_tpu.serving.engine import ServingEngine

    assert ServingEngine.page_nbytes(cut, 16) == 1 * 16 * 576 * 4
    carry_bytes = sum(int(np.prod(s)) * 4
                      for s, _ in kl.PAGED_FAMILY.carry_leaves(cut, 1))
    assert carry_bytes == 6 * (32 * 128 * 128 + 3 * 3 * 4096) * 4


def test_the_pool_snaps_to_its_bucket_and_seating_hands_on_its_changes(tiny):
    """This family goes through the scheduler every family goes through:
    the page pool's capacity is the power-of-two bucket of the batch's
    distinct pages at every size, and what ``_seat_batch`` returns (the
    sessions that sat down, the seats that moved) is what the carry stack
    is brought up to date with: those are the carries written, the rest
    are kept."""
    from oncilla_tpu.serving.engine import _pow2

    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(21)
    lens, new = (21, 3, 18, 2), (3, 14, 4, 12)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    pools, changes = [], []

    def watch(eng):
        batch_pool, seat_batch = eng._batch_pool, eng._seat_batch

        def pooled(batch):
            out = batch_pool(batch)
            pools.append((eng._pool[0][0].shape[0],
                          len({(e.page.page_id, e.version) for s in batch
                               for e in s.entries if not e.pending_fill})))
            return out

        def seated(batch):
            joined, moved = seat_batch(batch)
            assert all(s.seat is not None and s.carry is not None
                       for s in joined)
            changes.append((len(joined), len(moved), len(batch)))
            return joined, moved

        eng._batch_pool, eng._seat_batch = pooled, seated

    results, meta = serve(cfg, params, prompts, new, max_active=4,
                          max_batch=4, watch=watch)
    held_to_reference(results, prompts, params, conf, ref)
    assert all(cap == _pow2(max(rows, 1)) for cap, rows in pools)
    assert len({cap for cap, _ in pools}) > 1       # it grew and shrank
    written = sum(j + m for j, m, _ in changes)
    assert written == meta["carry"]["seats_written"] >= len(lens)
    assert (sum(b for _, _, b in changes) - written
            == meta["carry"]["seats_kept"] > 0)
