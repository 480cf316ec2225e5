"""The latent-attention / routed-expert / hyper-connection family
(``models/latent_moe.py``) against its plain reference
(``benchmark/references/latent_moe_hc.py``, which shares no code with it),
at a tiny size on the CPU in float32: the unpaged forward, prefill by pages
then decode through the pool, the two attention forms against each other,
the Sinkhorn mix, padding, and the dropless property."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import latent_moe as lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = 4   # page tokens


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_latent_moe_hc",
        os.path.join(ROOT, "benchmark", "references", "latent_moe_hc.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    cfg = lm.LatentMoeConfig.tiny()
    params = lm.init_params(jax.random.key(3), cfg)
    # Biases and the correction term are zeros as initialised: give them
    # values, so that every term of the equations is on the tested path.
    k1, k2 = jax.random.split(jax.random.key(5))
    params["hc_b"] = 0.5 * jax.random.normal(k1, params["hc_b"].shape)
    params["e_bias"] = 0.1 * jax.random.normal(k2, params["e_bias"].shape)
    return cfg, params, cfg.to_published(), load_reference()


def test_published_round_trip():
    cfg = lm.LatentMoeConfig.tiny()
    conf = cfg.to_published()
    assert conf["rope_scaling"]["factor"] == 4.0 and "dtype" not in conf
    assert conf["torch_dtype"] == "float32"
    assert lm.LatentMoeConfig.from_published(conf) == cfg
    assert cfg.n_expert_layers == 2 and cfg.first_k_dense_replace == 1
    assert cfg.hc_mult == 4 and cfg.num_experts_per_tok == 2


def test_forward_matches_reference_and_chooses_its_experts(tiny):
    cfg, params, conf, ref = tiny
    toks = np.random.default_rng(0).integers(1, cfg.vocab, (2, 37)).astype(
        np.int32)
    out, routing = jax.jit(
        lambda p, t: lm.forward(p, t, cfg, return_routing=True))(params, toks)
    want = ref.logits_at(params, toks, np.arange(37), conf)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)
    assert np.array_equal(np.sort(np.asarray(routing), axis=-1),
                          ref.experts_at(params, toks, conf))


def drive_paged(cfg, params, prompts, new_tokens, b_pad):
    """Prefill each prompt's whole pages with the page program, then take
    the rest and ``new_tokens`` greedy tokens through the fused step, all
    sessions in one batch padded to ``b_pad`` rows, over a pool the pages
    are written into. Returns every session's logits rows (one a position
    from its last whole page on) and the counts the programs handed back."""
    L, W = cfg.n_layers, cfg.latent_width
    dt = jnp.dtype(cfg.dtype)
    n = len(prompts)
    pool = jnp.zeros((16, L, 1, P, W), dt)
    tables, pos, consumed = [[] for _ in prompts], [0] * n, [0] * n
    rows = [[] for _ in prompts]
    page_counts, free = [], 0
    for s, prompt in enumerate(prompts):
        ctx = jnp.zeros((L, 1, 1, 0, W), dt)
        while len(prompt) - consumed[s] >= P:
            chunk = prompt[consumed[s]:consumed[s] + P]
            logits, tail, touched = lm.latent_decode_page_jit(
                params, jnp.asarray([chunk], jnp.int32),
                jnp.asarray([pos[s], 0], jnp.int32), ctx,
                jnp.zeros((L, 1, 1, P, W), dt), cfg)
            page_counts.append(int(touched))
            rows[s] = [np.asarray(logits[0, -1])]
            ctx = jnp.concatenate([ctx, tail], axis=3)
            pool = lm.latent_pool_write_row_jit(pool, tail, np.int32(free))
            tables[s].append(free)
            free += 1
            pos[s] += P
            consumed[s] += P
    tail = jnp.zeros((L, b_pad, 1, P, W), dt)
    tail_len = [0] * n
    last = [None] * n
    step_counts = []
    steps = max(len(p) - c for p, c in zip(prompts, consumed)) + new_tokens
    for _ in range(steps):
        toks, metas = [], []
        for s, prompt in enumerate(prompts):
            toks.append(prompt[consumed[s]] if consumed[s] < len(prompt)
                        else last[s])
            metas.append([pos[s], tail_len[s], len(tables[s]) * P, 0])
        mp = max(len(t) for t in tables) or 1
        table = np.zeros((b_pad, mp), np.int32)
        for s, t in enumerate(tables):
            table[s, :len(t)] = t
        pad = b_pad - n
        logits, tail, touched = lm.latent_decode_batch_step_jit(
            params, jnp.asarray(toks + [0] * pad, jnp.int32),
            jnp.asarray(metas + [[0, 0, 0, 0]] * pad, jnp.int32),
            np.int32(n), pool, jnp.asarray(table), tail, cfg)
        step_counts.append(int(touched))
        for s in range(n):
            if consumed[s] < len(prompts[s]):
                consumed[s] += 1
            rows[s].append(np.asarray(logits[s]))
            last[s] = int(np.argmax(logits[s]))
            pos[s] += 1
            tail_len[s] += 1
            if tail_len[s] == P:
                pool = lm.latent_pool_write_row_jit(
                    pool, tail[:, s:s + 1], np.int32(free))
                tables[s].append(free)
                free += 1
                tail_len[s] = 0
    return rows, last, page_counts, step_counts


def test_pages_then_pool_decode_match_reference(tiny):
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (10, 7, 3)]
    new = 6
    rows, _, page_counts, step_counts = drive_paged(
        cfg, params, prompts, new, b_pad=4)
    E, Le = cfg.n_routed_experts, cfg.n_expert_layers
    k = cfg.num_experts_per_tok
    assert all(k * 1 <= c <= min(E, P * k) * Le for c in page_counts)
    assert all(k * Le <= c <= min(E, 3 * k) * Le for c in step_counts)
    # Teacher-force the reference on what the program itself emitted. A
    # session's rows start at the last position of its last whole page (0
    # without one); from the last prompt token on they are greedy.
    for s, prompt in enumerate(prompts):
        got = np.stack(rows[s])
        first = max((len(prompt) // P) * P - 1, 0)
        outs = [int(r.argmax()) for r in got[len(prompt) - 1 - first:]]
        assert len(outs) >= new
        seq = np.asarray([prompt + outs[:-1]], np.int32)
        assert seq.shape[1] == first + len(got)
        want = ref.logits_at(params, seq, np.arange(first, seq.shape[1]),
                             conf)[0]
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_absorbed_decode_equals_expanded_attention_one_layer(tiny):
    cfg, params, _, _ = tiny
    rng = np.random.default_rng(2)
    S = 9
    h = jnp.asarray(rng.standard_normal((S, cfg.hidden_size)), jnp.float32)
    positions = jnp.arange(S)
    qn, qr, entry = lm.latent_qkv(h, params, 1, positions, cfg)
    causal = jnp.tril(jnp.ones((S, S), bool))
    expanded = lm.attend_expanded(qn, qr, entry, causal, params, 1, cfg)
    # Every position as a batch row of the decode form over the same cache.
    latent = jnp.broadcast_to(entry[None], (S, S, entry.shape[-1]))
    absorbed = lm.attend_absorbed(qn, qr, latent, causal, params, 1, cfg)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)


def test_hres_is_doubly_stochastic_and_the_clamp_is_reached(tiny):
    cfg, params, _, _ = tiny
    n = cfg.hc_mult
    lo, hi = cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.standard_normal((5, n, cfg.hidden_size)), jnp.float32)
    _, _, res = lm.hc_coefficients(X, params, 0, 1, cfg)
    assert res.shape == (5, n, n) and float(res.min()) > 0
    np.testing.assert_allclose(np.asarray(res.sum(-1)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=1e-5)
    # Logits far past the clamp: without it exp(200) is inf and the mix NaN.
    wild = 200.0 * (2.0 * jnp.eye(n) - 1.0)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(200.0)))
    mix = lm.sinkhorn(wild, cfg.hc_sinkhorn_iters, cfg.hc_eps, lo, hi)
    assert np.isfinite(np.asarray(mix)).all()
    np.testing.assert_allclose(np.asarray(mix.sum(-1)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mix.sum(-2)), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mix),
        np.asarray(lm.sinkhorn(jnp.clip(wild, lo, hi), cfg.hc_sinkhorn_iters,
                               cfg.hc_eps, -1e9, 1e9)))
    # The gate is strong enough that the model's own logits reach it.
    strong = dict(params)
    strong["hc_alpha"] = params["hc_alpha"].at[0, 1, 2].set(500.0)
    _, _, res = lm.hc_coefficients(X, strong, 0, 1, cfg)
    assert np.isfinite(np.asarray(res)).all()
    np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=1e-5)


def test_padded_rows_are_routed_nowhere_and_counted_nowhere(tiny):
    cfg, params, _, _ = tiny
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((8, cfg.hidden_size)), jnp.float32)
    real3 = jnp.arange(8) < 3
    y3, n3, idx = lm.expert_ffn(h, params, 0, real3, cfg)
    y_own, n_own, _ = lm.expert_ffn(h[:3], params, 0, jnp.ones((3,), bool),
                                    cfg)
    assert int(n3) == int(n_own) == len(np.unique(np.asarray(idx[:3])))
    np.testing.assert_allclose(np.asarray(y3[:3]), np.asarray(y_own),
                               atol=1e-6)
    # A padded row gets the shared expert's output and no routed one.
    shared = lm._swiglu(h, params["ws_gate"][0], params["ws_up"][0],
                        params["ws_down"][0], jnp.float32)
    np.testing.assert_allclose(np.asarray(y3[3:]), np.asarray(shared[3:]),
                               atol=1e-6)
    _, none, _ = lm.expert_ffn(h, params, 0, jnp.zeros((8,), bool), cfg)
    assert int(none) == 0
    # The fused step: the count of a batch of 2 padded to 8 is the count of
    # the same batch padded to 2.
    prompts = [rng.integers(1, cfg.vocab, 5).tolist() for _ in range(2)]
    _, last8, _, counts8 = drive_paged(cfg, params, prompts, 2, b_pad=8)
    _, last2, _, counts2 = drive_paged(cfg, params, prompts, 2, b_pad=2)
    assert counts8 == counts2 and last8 == last2
    k, Le = cfg.num_experts_per_tok, cfg.n_expert_layers
    assert max(counts8) <= 2 * k * Le


def test_no_token_is_dropped_when_all_choose_one_expert(tiny):
    cfg, params, conf, ref = tiny
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    herd = dict(params)
    # The correction term decides the choice (not the weights): every token
    # of every layer picks experts 5 and 2.
    bias = np.zeros((cfg.n_expert_layers, E), np.float32)
    bias[:, 5], bias[:, 2] = 50.0, 40.0
    herd["e_bias"] = jnp.asarray(bias)
    rng = np.random.default_rng(6)
    T = 24
    h = jnp.asarray(rng.standard_normal((T, cfg.hidden_size)), jnp.float32)
    y, n_hit, idx = lm.expert_ffn(h, herd, 1, jnp.ones((T,), bool), cfg)
    assert int(n_hit) == k and set(np.asarray(idx).ravel()) == {2, 5}
    # Every one of the 24 rows got both experts' output, by hand.
    s = jax.nn.sigmoid(h @ herd["w_router"][1])[:, [5, 2]]
    w = cfg.routed_scaling_factor * s / s.sum(-1, keepdims=True)
    want = sum(w[:, i:i + 1] * lm._swiglu(
        h, herd["w_gate_e"][1, e], herd["w_up_e"][1, e],
        herd["w_down_e"][1, e], jnp.float32) for i, e in enumerate((5, 2)))
    want = want + lm._swiglu(h, herd["ws_gate"][1], herd["ws_up"][1],
                             herd["ws_down"][1], jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    # And the whole model still agrees with the reference, which computes
    # every expert for every token.
    toks = rng.integers(1, cfg.vocab, (1, 19)).astype(np.int32)
    out = lm.forward(herd, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(
        np.asarray(out), ref.logits_at(herd, toks, np.arange(19), conf),
        atol=1e-4)
    assert np.array_equal(ref.experts_at(herd, toks, conf),
                          np.broadcast_to([2, 5], (2, 1, 19, 2)))


# -- through ServingEngine ---------------------------------------------------


def serve(cfg, params, prompts, new_tokens, *, hot, warm, share,
          max_active=4, max_batch=None, watch=None):
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.engine import Request, ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.prefix import PrefixCache
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = ServingEngine.page_nbytes(cfg, P)
    assert pb == cfg.n_layers * P * cfg.latent_width * 4
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=1 << 20))
    store = TieredPageStore(ctx, pb, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("latent"))
    eng = ServingEngine(params, cfg, store,
                        PrefixCache(store, P) if share else None,
                        page_tokens=P, max_active=max_active,
                        max_batch=max_batch, prefetch_workers=0,
                        name="latent", keep_logits=True)
    try:
        if watch is not None:
            watch(eng)
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=new_tokens))
        results = {r.tenant: r for r in eng.run()}
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    return results, meta


def test_engine_serves_it_through_hot_warm_cold_and_back(tiny):
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(7)
    base = rng.integers(1, cfg.vocab, 9).tolist()
    prompts = [base + rng.integers(1, cfg.vocab, n).tolist()
               for n in (5, 2, 8)] + [rng.integers(1, cfg.vocab, 3).tolist()]
    new = 9
    seatings = []

    def watch(eng):
        seat_batch = eng._seat_batch

        def seated(batch):
            stack = eng._tails
            changes = seat_batch(batch)
            assert len(eng._tails) == 1     # one leaf: the latent
            seatings.append((stack is not None
                             and stack[0].shape == eng._tails[0].shape,
                             [s.req.tenant for s in eng._seats]))
            return changes

        eng._seat_batch = seated

    # Two HOT and two WARM pages under four sessions of three to five
    # pages each: pages go down to the cold tier and come back.
    results, meta = serve(cfg, params, prompts, new, hot=2, warm=2,
                          share=True, max_batch=3, watch=watch)
    # A seat changed hands inside one stack: t3, which had lost its seat
    # alive with a token in its tail, sat down where another had finished.
    assert any(same and any(b != a for b, a in zip(before, after))
               for (_, before), (same, after) in zip(seatings, seatings[1:]))
    tails = meta["tails"]
    assert tails["seats_kept"] > tails["seats_written"] > 3
    assert (tails["seats_kept"] + tails["seats_written"]
            == meta["batch"]["size_sum"])
    hops = meta["moves"]["hops"]
    assert hops.get("hbm>host", 0) and hops.get("host>remote", 0)
    assert hops.get("remote>hbm", 0) or hops.get("remote>host", 0)
    assert meta["moves"]["promote"] > 0
    assert meta["prefix"]["hits"] > 0
    assert meta["batch"]["size_max"] == 3
    for i, prompt in enumerate(prompts):
        res = results[f"t{i}"]
        out = res.out_tokens
        got = np.stack(res.out_logits)
        assert len(out) == new and (got.argmax(-1) == out).all()
        seq = np.asarray([prompt + out[:-1]], np.int32)
        rows = np.arange(len(prompt) - 1, seq.shape[1])
        want = ref.logits_at(params, seq, rows, conf)[0]
        np.testing.assert_allclose(got, want, atol=1e-4)
    # The counters: every fused step routed k experts a real row a layer.
    moe = meta["moe"]
    k, Le, E = cfg.num_experts_per_tok, cfg.n_expert_layers, cfg.n_routed_experts
    assert moe["step_assignments"] == meta["batch"]["size_sum"] * k * Le
    assert (meta["batch"]["steps"] * k * Le <= moe["step_expert_rows"]
            <= min(moe["step_assignments"], meta["batch"]["steps"] * E * Le))
    assert moe["page_count"] == meta["batch"]["prefill_chunks"] > 0
    assert (moe["page_count"] * k * Le <= moe["page_expert_rows"]
            <= moe["page_count"] * min(E, P * k) * Le)


def test_dense_family_counts_no_experts():
    from oncilla_tpu.models import LlamaConfig
    from oncilla_tpu.serving.engine import DENSE_FAMILY, family_of

    cfg = LlamaConfig.tiny()
    assert family_of(cfg) is DENSE_FAMILY
    assert family_of(lm.LatentMoeConfig.tiny()) is lm.PAGED_FAMILY
    assert DENSE_FAMILY.leaf_shape(cfg, 8) == (
        cfg.n_layers, 1, cfg.n_kv_heads, 8, cfg.head_dim)
    tiny_cfg = lm.LatentMoeConfig.tiny()
    assert lm.PAGED_FAMILY.leaf_shape(tiny_cfg, 8, batch=3) == (
        tiny_cfg.n_layers, 3, 1, 8, tiny_cfg.latent_width)
