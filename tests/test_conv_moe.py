"""The gated short-convolution / grouped-query-attention / routed-expert
family (``models/conv_moe.py``) against its plain reference
(``benchmark/references/conv_gqa_moe.py``, which shares no code with it), at
a tiny size on the CPU in float32: the whole forward, the convolution's two
forms over chunk boundaries, the router with and without its bias, an expert
layer with no shared expert, the bytes of the cut, and prefill by pages then
decode by the fused step through ``ServingEngine`` with a carry that follows
its session through seats."""

import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import conv_moe as cm
from oncilla_tpu.models import latent_moe as lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmark", "references", "conv_gqa_moe.py")
P = 4   # page tokens

# Float32 on the CPU against a float32 reference at ``highest`` precision:
# what is left is the order of the sums (a page's tokens through a layer
# together, the experts in a loop), a few 1e-6 on logits of order 4.
ATOL = 1e-4


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded(cfg, seed=3):
    """Weights with every constant leaf given values, so that every term
    of the equations is on the tested path."""
    params = cm.init_params(jax.random.key(seed), cfg)
    keys = jax.random.split(jax.random.key(seed + 2), 5)
    for k, name in zip(keys, ("q_norm", "k_norm", "ln_op", "ln_ffn",
                              "ln_out")):
        params[name] = 1.0 + 0.1 * jax.random.normal(k, params[name].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = cm.ConvMoeConfig.tiny()
    return (cfg, seeded(cfg), cfg.to_published(),
            load(REFERENCE, "reference_conv_gqa_moe"))


def test_published_round_trip_and_the_layer_pattern():
    cfg = cm.ConvMoeConfig.tiny()
    conf = cfg.to_published()
    assert conf["torch_dtype"] == "float32" and "dtype" not in conf
    assert conf["rope_parameters"] == {"rope_theta": 100.0,
                                       "rope_type": "default"}
    assert cm.ConvMoeConfig.from_published(conf) == cfg
    assert cfg.conv_layers == (0, 1, 3, 4) and cfg.attn_layers == (2, 5)
    full = cm.ConvMoeConfig()
    assert len(full.conv_layers) == 30 and len(full.attn_layers) == 10
    assert full.attn_layers[:3] == (2, 6, 10) and full.head_dim == 64
    # a cut in depth reads the head of the published list
    pub = full.to_published()
    pub["num_hidden_layers"] = 10
    del pub["head_dim"]
    cut = cm.ConvMoeConfig.from_published(pub)
    assert cut.attn_layers == (2, 6) and len(cut.conv_layers) == 8
    assert cut.head_dim == 64 and cut.n_expert_layers == 8
    assert cut.experts_held == (0, 64) == (0, cut.n_routed_experts)
    with pytest.raises(ValueError, match="attention layer"):
        dataclasses.replace(full, num_hidden_layers=2,
                            layer_types=("conv", "conv"))


def test_the_whole_forward_matches_reference_and_chooses_its_experts(tiny):
    cfg, params, conf, ref = tiny
    toks = np.random.default_rng(0).integers(1, cfg.vocab, (2, 37)).astype(
        np.int32)
    out, routing = jax.jit(
        lambda p, t: cm.forward(p, t, cfg, return_routing=True))(params, toks)
    want = ref.logits_at(params, toks, np.arange(37), conf)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(out), want, atol=ATOL)
    assert np.array_equal(np.sort(np.asarray(routing), axis=-1),
                          ref.experts_at(params, toks, conf))


def test_the_reference_imports_nothing_from_the_program():
    with open(REFERENCE) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "oncilla" in line]
    assert "oncilla_tpu" not in source.split('"""', 2)[2]


def test_reference_lengths_of_one_block_share_their_executables(tiny):
    """The reference runs a sequence at its length rounded up to
    ``SEQ_BLOCK``: a second length of the same block builds no executable,
    and what is appended changes no logit before it."""
    cfg, params, conf, ref = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(1, cfg.vocab, (1, n)).astype(np.int32)
                     for n in (37, 23))
    ref.logits_at(params, first, np.arange(3, 37), conf)
    built = []
    # JAX has no way to take one listener off again: this one outlives the
    # test and counts what is built while it is watched, nothing after.
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _s, **kw: built.append(name))
    ref.logits_at(params, second, np.arange(23), conf)
    assert not [b for b in built if "backend_compile" in b]
    longer = np.concatenate([first, second], axis=1)
    np.testing.assert_allclose(
        ref.logits_at(params, longer, np.arange(37), conf),
        ref.logits_at(params, first, np.arange(37), conf), atol=1e-5)


# -- the convolution over chunk boundaries ---------------------------------------


def conv_by_definition(h, params, l, cfg):
    """``c_t = sum_j w[j] v_{t-(K-1)+j}`` written as the sum it is, a token
    at a time, with numpy."""
    K, D = cfg.conv_L_cache, cfg.hidden_size
    bcz = np.asarray(h, np.float64) @ np.asarray(params["conv_in"][l],
                                                 np.float64)
    b, c, z = bcz[:, :D], bcz[:, D:2 * D], bcz[:, 2 * D:]
    v = b * z
    taps = np.asarray(params["conv_k"][l], np.float64)
    out = np.zeros_like(v)
    for t in range(len(v)):
        for j in range(K):
            s = t - (K - 1) + j
            if s >= 0:
                out[t] += taps[j] * v[s]
    return (c * out) @ np.asarray(params["conv_out"][l], np.float64), v


@pytest.mark.parametrize("cuts", [
    (1,), (2,), (1, 1, 1), (4, 4, 3), (4, 1), (2, 4, 4, 1), (11,)],
    ids=lambda c: "+".join(map(str, c)))
def test_conv_chunks_and_steps_carry_v_over_every_boundary(tiny, cuts):
    """A sequence cut into chunks (a prompt of 1 token, of ``K - 1``, a
    partial last page) through :func:`conv_chunk`, and token by token
    through :func:`conv_step`, is the convolution over the whole
    sequence; the carry at the end is its last ``K - 1`` products."""
    cfg, params, _, _ = tiny
    S, l = sum(cuts), 1
    h = jax.random.normal(jax.random.key(7), (S, cfg.hidden_size))
    want, v = conv_by_definition(h, params, l, cfg)
    carry = jnp.zeros((cfg.conv_L_cache - 1, cfg.hidden_size))
    ys, at = [], 0
    for n in cuts:
        y, carry = cm.conv_chunk(h[at:at + n], carry, params, l, cfg)
        ys.append(y)
        at += n
    np.testing.assert_allclose(np.concatenate(ys), want, atol=1e-5)
    padded = np.concatenate([np.zeros((cfg.conv_L_cache - 1,
                                       cfg.hidden_size)), v])
    np.testing.assert_allclose(carry, padded[-(cfg.conv_L_cache - 1):],
                               atol=1e-5)
    rows = jnp.zeros((1, cfg.conv_L_cache - 1, cfg.hidden_size))
    ys = []
    for t in range(S):
        y, rows = cm.conv_step(h[t:t + 1], rows, params, l, cfg)
        ys.append(y)
    np.testing.assert_allclose(np.concatenate(ys), want, atol=1e-5)
    np.testing.assert_allclose(rows[0], carry, atol=1e-6)


def test_a_padding_row_of_the_step_keeps_its_carry(tiny):
    cfg, params, _, _ = tiny
    fam = cm.PAGED_FAMILY
    B = 4
    shapes = fam.leaf_shapes(cfg, P, B)
    tails = tuple(jnp.zeros(s) for s in shapes)
    rows = tuple(jnp.zeros((2, s[0]) + s[2:]) for s in fam.leaf_shapes(cfg, P))
    (shape, dt), = fam.carry_leaves(cfg, B)
    carry = jax.random.normal(jax.random.key(1), shape, dt)
    before = np.asarray(carry)
    _, _, _, (after,) = fam.step(
        params, jnp.ones((B,), jnp.int32), jnp.zeros((B, 4), jnp.int32), 3,
        rows, jnp.zeros((B, 1), jnp.int32), tails, cfg, (carry,))
    after = np.asarray(after)
    assert np.array_equal(after[:, 3], before[:, 3])
    assert not np.array_equal(after[:, :3], before[:, :3])
    # rolled by one: yesterday's newest product is today's oldest
    np.testing.assert_array_equal(after[:, :3, 0], before[:, :3, 1])


# -- the router, and a layer with no shared expert ----------------------------------


@pytest.mark.parametrize("bias", ["seeded", "zero"])
def test_router_weights_are_the_references_with_and_without_bias(tiny, bias):
    """``lm.route`` as this family drives it (sigmoid, the bias for the
    choice alone, the chosen scores over their sum + 1e-6) against the
    reference's router; with the bias the choice differs from the top
    scores, without it it is them."""
    cfg, params, conf, ref = tiny
    params = dict(params)
    if bias == "zero":
        params["e_bias"] = jnp.zeros_like(params["e_bias"])
    else:
        params["e_bias"] = 0.3 * jax.random.normal(
            jax.random.key(9), params["e_bias"].shape)
    h = jax.random.normal(jax.random.key(2), (29, cfg.hidden_size))
    idx, weights, hit = lm.route(h, params, 0, jnp.ones((29,), bool), cfg)
    want_w, want_ids = ref._route(
        h[None], params["w_router"][0], params["e_bias"][0],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    assert np.array_equal(np.sort(np.asarray(idx), -1), want_ids[0])
    np.testing.assert_allclose(weights, want_w[0], atol=1e-6)
    s = jax.nn.sigmoid(h @ params["w_router"][0])
    top = np.sort(np.asarray(jax.lax.top_k(s, cfg.num_experts_per_tok)[1]),
                  -1)
    same = np.array_equal(np.sort(np.asarray(idx), -1), top)
    assert same == (bias == "zero")
    # the weights are the scores themselves, never the biased ones: they
    # sum to 1 less the 1e-6's share
    sums = np.asarray(weights.sum(-1))
    assert (sums < 1.0).all() and (sums > 1.0 - 1e-5).all()
    assert int(hit.sum()) == len(np.unique(np.asarray(idx)))


def test_the_norm_constant_is_this_familys_alone():
    """``router_norm_eps`` is read where a config states it; a family that
    states none divides by the plain sum, as before."""
    cfg = cm.ConvMoeConfig.tiny()
    assert cfg.router_norm_eps == 1e-6
    params = seeded(cfg)
    h = 0.01 * jax.random.normal(jax.random.key(4), (5, cfg.hidden_size))
    real = jnp.ones((5,), bool)
    _, w_eps, _ = lm.route(h, params, 0, real, cfg)
    _, w_big, _ = lm.route(
        h, params, 0, real, dataclasses.replace(cfg, router_norm_eps=1.0))
    assert float(jnp.abs(w_eps.sum(-1) - 1).max()) < 1e-5
    assert float(w_big.sum(-1).max()) < 0.75
    for other in (lm.LatentMoeConfig.tiny(),):
        assert not hasattr(other, "router_norm_eps")


def test_an_expert_layer_with_no_shared_expert_is_the_references(tiny):
    """``lm.expert_ffn`` over this family's leaves (no ``ws_*``) is the
    reference's expert layer; given a shared expert's leaves it adds that
    expert, as it does for the families that have one."""
    cfg, params, conf, ref = tiny
    assert not [k for k in params if k.startswith("ws_")]
    h = jax.random.normal(jax.random.key(6), (13, cfg.hidden_size))
    real = jnp.ones((13,), bool)
    y, n_hit, idx = lm.expert_ffn(h, params, 1, real, cfg)
    ep = {k: params[k][1] for k in ref.ROUTER_LEAVES + ref.EXPERT_LEAVES}
    with jax.default_matmul_precision("highest"):
        want, ids = ref.expert_layer(h[None], ep, conf)
    np.testing.assert_allclose(y, want[0], atol=1e-5)
    assert int(n_hit) == len(np.unique(np.asarray(ids)))
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    k1, k2, k3 = jax.random.split(jax.random.key(8), 3)
    shared = {"ws_gate": 0.1 * jax.random.normal(k1, (cfg.n_expert_layers,
                                                      D, F)),
              "ws_up": 0.1 * jax.random.normal(k2, (cfg.n_expert_layers,
                                                    D, F)),
              "ws_down": 0.1 * jax.random.normal(k3, (cfg.n_expert_layers,
                                                      F, D))}
    y2, _, _ = lm.expert_ffn(h, {**params, **shared}, 1, real, cfg)
    extra = lm._swiglu(h, shared["ws_gate"][1], shared["ws_up"][1],
                       shared["ws_down"][1], jnp.float32)
    np.testing.assert_allclose(y2 - y, extra, atol=1e-5)


# -- the bytes of the cut ---------------------------------------------------------


def test_param_spec_bytes_are_the_bytes_models_at_the_benchmarks_cut():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-d10.json")) as f:
        conf = json.load(f)
    bm = load(os.path.join(ROOT, "benchmark", "bytes_models",
                           "conv_gqa_moe.py"), "bytes_conv_gqa_moe")
    cfg = cm.ConvMoeConfig.from_published(conf)
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv",
                               "conv", "conv", "full_attention", "conv",
                               "conv", "conv")
    spec = cm.param_spec(cfg)
    nbytes = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                 for shape, _, dt in spec.values())
    assert nbytes == bm.weight_bytes(conf) == 10_536_365_056
    assert sum(math.prod(s) for s, _, _ in spec.values()) == 5_267_090_176
    experts = sum(math.prod(spec[k][0]) * 2
                  for k in ("w_gate_e", "w_up_e", "w_down_e"))
    assert bm.fixed_weight_bytes(conf) == nbytes - experts
    assert experts == 8 * 64 * bm.expert_bytes(conf)
    # a page, and a carry, which is a prefix extent's snapshot: 128 KiB both
    from oncilla_tpu.serving.engine import ServingEngine

    assert ServingEngine.page_nbytes(cfg, 16) == bm.page_bytes(conf, 16) == (
        128 << 10)
    (shape, dt), = cm.PAGED_FAMILY.carry_leaves(cfg, 1)
    assert shape == (8, 1, 2, 2048)
    assert math.prod(shape) * jnp.dtype(dt).itemsize == bm.carry_bytes(
        conf) == 128 << 10
    # the least a step must move: 4 experts a layer, one seat
    assert bm.decode_step_bytes(conf, 0) == (
        bm.fixed_weight_bytes(conf) + 8 * 4 * bm.expert_bytes(conf)
        + 2 * bm.carry_bytes(conf))
    assert bm.step_bytes_counted(conf, 100, 10, 3) == (
        bm.fixed_weight_bytes(conf) + 10 * bm.expert_bytes(conf)
        + 100 * 4096 + 6 * bm.carry_bytes(conf))


# -- through ServingEngine ---------------------------------------------------


def serve(cfg, params, prompts, new_tokens, *, hot=256, warm=4, share=False,
          max_active=4, max_batch=None, watch=None, rounds=1):
    """Run ``prompts`` through an engine, ``rounds`` times over (a later
    round finds what an earlier one published). Returns the results of
    every round and the counters at the end."""
    import oncilla_tpu as ocm
    from oncilla_tpu.serving.engine import Request, ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.prefix import PrefixCache
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = ServingEngine.page_nbytes(cfg, P)
    assert pb == len(cfg.attn_layers) * 2 * cfg.num_key_value_heads * P * (
        cfg.head_dim * 4)
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=4 << 20))
    store = TieredPageStore(ctx, pb, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("conv"))
    try:
        eng = ServingEngine(params, cfg, store,
                            PrefixCache(store, P) if share else None,
                            page_tokens=P, max_active=max_active,
                            max_batch=max_batch, prefetch_workers=0,
                            name="conv", keep_logits=True)
    except BaseException:
        store.close()
        ctx.tini()
        raise
    out = []
    try:
        if watch is not None:
            watch(eng)
        for _ in range(rounds):
            for i, (p, n) in enumerate(zip(prompts, new_tokens)):
                eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                                   max_new_tokens=n))
            out.append({r.tenant: r for r in eng.run()})
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    return out, meta


def held_to_reference(results, prompts, params, conf, ref, atol=ATOL):
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
# prompts of 1 and of K - 1 tokens and under a page, a partial last page, a
# batch that pads (3 of 4 rows), more sessions than seats.
SCHEDULES = {
    "one-session": ((11,), (7,), 1, 1),
    "padding-rows": ((9, 3, 14), (6, 9, 5), 3, 4),
    "seats-change-hands": ((13, 6, 9, 2, 17, 1), (9, 4, 7, 6, 5, 3), 6, 2),
    "whole-pages": ((8, 12), (5, 5), 2, 2),
}


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_engine_prefill_then_decode_matches_the_reference(tiny, name):
    cfg, params, conf, ref = tiny
    lens, new, max_active, max_batch = SCHEDULES[name]
    rng = np.random.default_rng(len(name))
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    (results,), meta = serve(cfg, params, prompts, new,
                             max_active=max_active, max_batch=max_batch)
    assert all(len(results[f"t{i}"].out_tokens) == n
               for i, n in enumerate(new))
    held_to_reference(results, prompts, params, conf, ref)
    carry, tails = meta["carry"], meta["tails"]
    assert (carry["seats_kept"] + carry["seats_written"]
            == tails["seats_kept"] + tails["seats_written"]
            == meta["batch"]["size_sum"])
    # the family's expert counts come back through the aux, as the other
    # expert families'
    moe = meta["moe"]
    assert moe["step_expert_rows"] > 0
    assert moe["step_assignments"] == (
        meta["batch"]["size_sum"] * cfg.num_experts_per_tok
        * cfg.n_expert_layers)
    pages = sum(n // P for n in lens)
    assert moe["page_count"] == pages == meta["batch"]["prefill_chunks"]
    assert (moe["page_expert_rows"] > 0) == (pages > 0)
    # with the cache off nothing is snapshotted, adopted or restored
    assert meta["prefix"]["carry_snapshots"] == 0
    assert meta["prefix"]["adoptions"] == 0


def test_only_the_attention_layers_keep_pages(tiny):
    cfg, params, _, _ = tiny
    fam = cm.PAGED_FAMILY
    assert fam.cached_layers(cfg) == 2 and fam.kinds is None
    assert fam.leaf_shapes(cfg, P) == ((2, 1, 2, P, 16),) * 2
    assert fam.carry_leaves(cfg, 3) == (((4, 3, 2, 64), jnp.float32),)


def test_the_page_programs_context_is_padded_to_a_power_of_two(tiny):
    """No program a context length: a session's pages are joined in one
    dispatch up to the power of two above, and the page program masks what
    is past ``pos0``."""
    cfg, params, conf, ref = tiny
    seen = []

    def watch(eng):
        page = eng.family.page

        def counting(params, toks, meta, ctx, *rest):
            seen.append((int(meta[0]) // P, ctx[0].shape[3] // P))
            return page(params, toks, meta, ctx, *rest)

        eng.family = dataclasses.replace(eng.family, page=counting)

    prompt = np.random.default_rng(2).integers(1, cfg.vocab, 7 * P + 2)
    (results,), _ = serve(cfg, params, [prompt.tolist()], (3,), watch=watch)
    assert seen == [(0, 0), (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (6, 8)]
    held_to_reference(results, [prompt.tolist()], params, conf, ref)


def test_the_programs_name_their_mechanisms(tiny):
    """``conv``, ``attn`` and ``experts`` are scopes of both programs (what
    a device trace groups their operations by)."""
    cfg, params, _, _ = tiny
    fam = cm.PAGED_FAMILY
    B, N = 2, 4

    def leaves(batch, tokens=P):
        return tuple(jnp.zeros(s, jnp.float32)
                     for s in fam.leaf_shapes(cfg, tokens, batch))

    def carry(batch):
        (shape, dt), = fam.carry_leaves(cfg, batch)
        return jnp.zeros(shape, dt)

    rows = tuple(jnp.zeros((N, s[0]) + s[2:], jnp.float32)
                 for s in fam.leaf_shapes(cfg, P))
    step = cm.conv_decode_batch_step_jit.lower(
        params, jnp.zeros((B,), jnp.int32), jnp.zeros((B, 4), jnp.int32),
        np.int32(B), rows, jnp.zeros((B, 2), jnp.int32), leaves(B), carry(B),
        cfg)
    page = cm.conv_decode_page_jit.lower(
        params, jnp.zeros((1, P), jnp.int32), jnp.zeros((2,), jnp.int32),
        leaves(1, 2 * P), leaves(1), carry(1), cfg)
    for lowered in (step, page):
        text = lowered.as_text(debug_info=True)
        for scope in ("conv", "attn", "experts"):
            assert scope in text, scope
