"""The window family in its Mellum2 shape (``models/swa_moe.py`` with no
gate, one head count, every layer sparse, softmax-scored experts all held,
no shared expert) against its plain reference
(``benchmark/references/swa_gqa_softmax_moe.py``, which shares no code with
it), at a tiny size on the CPU in float32: the softmax router against its
formula, the builds that take a mechanism out, the layers unpaged, prefill
then decode through ``ServingEngine`` across the window's edge, and the
counters the steps hand back against a recount."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import latent_moe as lm
from oncilla_tpu.models import swa_moe as sm
from oncilla_tpu.models.kv_paging import PageKind
from test_swa_moe import P, held_to_reference, serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
REFERENCE = os.path.join(BENCH, "references", "swa_gqa_softmax_moe.py")
CONFIG = os.path.join(BENCH, "configs", "mellum2-12b-a2.5b-d8.json")


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg) -> dict:
    """The tiny config as a Mellum-shaped file reads: one head count."""
    return {**cfg.to_published(),
            "num_attention_heads": cfg.num_attention_heads_per_layer[0]}


@pytest.fixture(scope="module")
def tiny():
    cfg = sm.SwaMoeConfig.tiny_softmax()
    params = sm.init_params(jax.random.key(3), cfg)
    return cfg, params, published(cfg), load(REFERENCE, "ref_swa_softmax")


def test_the_tiny_config_is_one_period_with_every_mechanism_off():
    cfg = sm.SwaMoeConfig.tiny_softmax()
    assert cfg.window_layers == (0, 1, 2) and cfg.full_layers == (3,)
    assert cfg.first_k_dense_replace == 0 and cfg.n_expert_layers == 4
    assert cfg.experts_held == (0, 16) == (0, cfg.n_routed_experts)
    assert (cfg.gating, cfg.scoring_func) == ("none", "softmax")
    assert sm.SwaMoeConfig.from_published(cfg.to_published()) == cfg
    assert sm.PAGED_FAMILY.page_kinds(cfg) == (
        PageKind(1, None, 2), PageKind(3, 10, 2))
    # Laguna's tiny shape is what it was
    assert sm.SwaMoeConfig.tiny().gating == "per-head"
    assert sm.SwaMoeConfig.tiny().scoring_func == "sigmoid"
    for bad in ({"gating": "per-layer"}, {"scoring_func": "sqrtsoftplus"}):
        with pytest.raises(ValueError, match=next(iter(bad))):
            sm.SwaMoeConfig.tiny_softmax(**bad)


# -- the router ----------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_softmax_route_is_the_written_formula(tiny, seed):
    """p = softmax(h Wr) over every output, the k largest, p / sum(chosen);
    padded rows weigh nothing and choose nothing."""
    cfg, params, _, _ = tiny
    rng = np.random.default_rng(seed)
    T, j, k = 12, seed % cfg.n_expert_layers, cfg.num_experts_per_tok
    h = rng.standard_normal((T, cfg.hidden_size)).astype(np.float32)
    real = np.arange(T) < 9
    idx, weights, hit = lm.route(jnp.asarray(h), params, j,
                                 jnp.asarray(real), cfg)
    logits = h.astype(np.float64) @ np.asarray(params["w_router"][j],
                                               np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1)[:, :k]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(top, -1))
    want = np.zeros_like(p)
    for t in range(T):
        if real[t]:
            want[t, top[t]] = p[t, top[t]] / p[t, top[t]].sum()
    np.testing.assert_allclose(np.asarray(weights), want, atol=1e-6)
    assert np.asarray(weights)[real].sum(-1) == pytest.approx(1.0, abs=1e-6)
    assert np.array_equal(np.asarray(hit),
                          np.isin(np.arange(cfg.router_experts), top[real]))
    # no bias is read and nothing scales
    assert "e_bias" not in params


def test_the_sigmoid_route_is_untouched_by_the_score_function_field():
    """A Laguna-shaped config routes as it always did: the bias moves the
    choice, the weights are scaled."""
    cfg = sm.SwaMoeConfig.tiny()
    params = sm.init_params(jax.random.key(1), cfg)
    params["e_bias"] = 0.3 * jax.random.normal(jax.random.key(2),
                                               params["e_bias"].shape)
    h = jax.random.normal(jax.random.key(4), (6, cfg.hidden_size))
    idx, weights, _ = lm.route(h, params, 0, jnp.ones((6,), bool), cfg)
    s = jax.nn.sigmoid(h @ params["w_router"][0])
    top = np.asarray(jax.lax.top_k(s + params["e_bias"][0], 4)[1])
    assert np.array_equal(np.asarray(idx), top)
    assert np.asarray(weights).sum(-1) == pytest.approx(
        cfg.moe_routed_scaling_factor, rel=1e-5)


# -- the builds without a gate, a shared expert, a dense layer --------------------


OFF = {
    "gate": ({"gating": "none"}, ("f_wg", "w_wg")),
    "shared": ({"shared_expert_intermediate_size": 0},
               ("ws_gate", "ws_up", "ws_down")),
    "dense": ({"mlp_layer_types": ("sparse",) * 5},
              ("w_gate", "w_up", "w_down")),
    "bias": ({"scoring_func": "softmax"}, ("e_bias",)),
}


@pytest.mark.parametrize("name", sorted(OFF))
def test_a_build_without_a_mechanism_has_none_of_its_leaves(name):
    """Each switch alone on Laguna's tiny shape: its leaves are gone from
    ``param_spec`` and the weights, the rest stay, and the unpaged forward
    runs on what is left."""
    kw, leaves = OFF[name]
    on = sm.SwaMoeConfig.tiny()
    off = sm.SwaMoeConfig.tiny(**kw)
    spec_on, spec_off = sm.param_spec(on), sm.param_spec(off)
    assert set(leaves) <= set(spec_on)
    assert set(spec_on) - set(spec_off) == set(leaves)
    params = sm.init_params(jax.random.key(0), off)
    assert not set(leaves) & set(params)
    logits = sm.forward(params, jnp.ones((1, 7), jnp.int32), off)
    assert logits.shape == (1, 7, off.vocab) and np.isfinite(logits).all()


def lowered(cfg, params):
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
    return step, page


def test_both_programs_have_a_router_scope_and_no_gate(tiny):
    cfg, params, _, _ = tiny
    for low in lowered(cfg, params):
        text = low.as_text(debug_info=True)
        for scope in ("attn_full", "attn_window", "router", "experts"):
            assert scope in text, scope
        assert "/gate/" not in text and "gate/" not in text.replace(
            "w_gate", "")
    # Laguna's programs keep their gate and gain the router scope
    laguna = sm.SwaMoeConfig.tiny()
    for low in lowered(laguna, sm.init_params(jax.random.key(0), laguna)):
        text = low.as_text(debug_info=True)
        assert "gate" in text and "router" in text


# -- the layers against the reference ----------------------------------------------


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


def test_rotary_turns_the_whole_head_by_yarn_in_full_layers(tiny):
    cfg, _, conf, ref = tiny
    for full, kind in ((True, sm.FULL), (False, sm.WINDOW)):
        inv_freq, factor = sm.rope_of(cfg, full)
        width, want_factor, freqs = ref.rope_of(conf, kind)
        assert 2 * len(inv_freq) == width == cfg.head_dim
        assert factor == want_factor
        np.testing.assert_allclose(inv_freq, freqs, rtol=1e-6)
    ratio = sm.rope_of(cfg, True)[0] / 100.0 ** -(np.arange(0, 16, 2) / 16)
    assert ratio[0] == 1.0 and abs(ratio[-1] - 0.25) < 1e-6
    assert ((ratio > 0.26) & (ratio < 0.99)).any()
    # the published numbers: all 128 values, factor 16, cos and sin x 1.2773
    with open(CONFIG) as f:
        file = json.load(f)
    width, factor, _ = ref.rope_of(file, sm.FULL)
    assert width == 128 and abs(factor - 1.2773) < 1e-4


def test_the_reference_imports_nothing_from_the_program():
    with open(REFERENCE) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "oncilla" in line]
    assert "oncilla_tpu" not in source.split('"""', 2)[2]


# -- through ServingEngine ------------------------------------------------------------


# (prompt lengths, new tokens, max_active, max_batch). The window is 10
# positions, 2.5 pages of 4: every prompt of the first three is past it.
SCHEDULES = {
    "one-session-past-the-window": ((23,), (9,), 1, 1),
    "file-sized-prompts": ((37, 29, 21), (7, 12, 9), 3, 3),
    "seats-change-hands": ((26, 18, 33, 14), (9, 6, 5, 11), 4, 2),
    "decode-crosses-the-edge": ((5, 9), (22, 17), 2, 2),
}


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_engine_prefill_then_decode_matches_the_reference(tiny, name):
    cfg, params, conf, ref = tiny
    lens, new, max_active, max_batch = SCHEDULES[name]
    rng = np.random.default_rng(len(name))
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    results, meta = serve(cfg, params, prompts, new, max_active=max_active,
                          max_batch=max_batch)
    held_to_reference(results, prompts, params, conf, ref)
    ends = [n + m - 1 for n, m in zip(lens, new)]
    window = meta["window"]
    assert window["pages_shipped"] == sum(e // P for e in ends)
    assert window["pages_dropped"] == sum(
        max((e // P * P - cfg.sliding_window) // P, 0) for e in ends) > 0
    moe = meta["moe"]
    K = sm.PAGED_FAMILY.chunk_pages
    assert moe["page_count"] == meta["batch"]["prefill_chunks"] == sum(
        -(-(n // P) // K) for n in lens)
    assert meta["prefill"]["pages"] == sum(n // P for n in lens)
    assert moe["step_assignments"] == (meta["batch"]["size_sum"]
                                       * cfg.num_experts_per_tok * 4)


def chunk_context(cfg, c: int) -> int:
    """(layer, position) pairs of the context the page program of a chunk
    that starts at page ``c`` (position c P) is handed: every earlier
    position of a full layer; of a window layer the pages not yet dropped
    (a page that starts at s goes once s + P <= pos - window), a window at
    most."""
    dropped = max((c * P - cfg.sliding_window) // P, 0)
    return (len(cfg.full_layers) * c * P + len(cfg.window_layers)
            * min((c - dropped) * P, cfg.sliding_window))


def test_the_counters_are_a_recount_of_the_routing_and_the_contexts(tiny):
    """One session, one seat: a fused step's distinct (layer, expert) pairs
    are k a layer; a page program's are the distinct experts the tokens of
    its pages chose (up to ``chunk_pages`` pages of P), as the reference
    routes them; its context is the recount of :func:`chunk_context`."""
    cfg, params, conf, ref = tiny
    prompt = np.random.default_rng(7).integers(1, cfg.vocab, 39).tolist()
    new = 8
    results, meta = serve(cfg, params, [prompt], [new], max_active=1,
                          max_batch=1)
    out = results["t0"].out_tokens
    seq = np.asarray([prompt + out[:-1]], np.int32)
    routing = ref.experts_at(params, seq, conf)[:, 0]      # (L, S, k)
    pages, K = len(prompt) // P, sm.PAGED_FAMILY.chunk_pages
    starts = range(0, pages, K)
    k, Le = cfg.num_experts_per_tok, cfg.n_expert_layers
    moe, kv = meta["moe"], meta["kv"]
    assert moe["page_count"] == len(starts) == 2
    assert moe["page_expert_rows"] == sum(
        len(np.unique(routing[j, c * P:min(c + K, pages) * P]))
        for c in starts for j in range(Le))
    steps = meta["batch"]["steps"]
    assert steps == len(prompt) - pages * P + new - 1
    assert moe["step_expert_rows"] == steps * k * Le
    assert moe["step_assignments"] == steps * k * Le
    assert kv["page_positions_read"] == sum(
        chunk_context(cfg, c) for c in starts)
    # past the window a window layer reads its window's worth, no more
    assert chunk_context(cfg, starts[-1]) < cfg.n_layers * starts[-1] * P


def test_the_page_context_counter_sums_over_sessions_and_families(tiny):
    """Several sessions sum; a family of one kind counts every layer over
    every earlier position."""
    cfg, params, _, _ = tiny
    lens = (37, 21, 13)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    _, meta = serve(cfg, params, prompts, (3, 3, 3), max_active=3,
                    max_batch=3)
    K = sm.PAGED_FAMILY.chunk_pages
    assert meta["kv"]["page_positions_read"] == sum(
        chunk_context(cfg, c) for n in lens for c in range(0, n // P, K))
    from test_swa_moe import _family_case
    dense_cfg, dense_params = _family_case("dense")
    _, meta = serve(dense_cfg, dense_params, [list(range(1, 14))], [2],
                    max_active=1, max_batch=1)
    assert meta["kv"]["page_positions_read"] == (
        dense_cfg.n_layers * P * sum(range(13 // P)))


# -- the bytes model at the published widths ----------------------------------------


def test_param_spec_is_the_bytes_models_count_at_the_published_widths():
    """The configuration's 8-layer cut: every leaf of ``param_spec`` at
    the published widths is what the benchmark's bytes model counts,
    3.795 B parameters and 7.59 GB."""
    with open(CONFIG) as f:
        file = json.load(f)
    adapter = load(os.path.join(BENCH, "families", "swa_gqa_softmax_moe.py"),
                   "adapter_swa_softmax")
    bm = load(os.path.join(BENCH, "bytes_models", "swa_gqa_softmax_moe.py"),
              "bytes_swa_softmax")
    cfg = adapter.program_config(file)
    spec = sm.param_spec(cfg)
    size = {k: math.prod(shape) * (4 if dt == "float32" else 2)
            for k, (shape, _, dt) in spec.items()}
    assert bm.weight_bytes(file) == sum(size.values())
    routed = sum(size[k] for k in ("w_gate_e", "w_up_e", "w_down_e"))
    assert bm.expert_bytes(file) * 64 * 8 == routed
    count = sum(math.prod(shape) for shape, _, _ in spec.values())
    assert 3.794e9 < count < 3.796e9
    assert 7.58e9 < bm.weight_bytes(file) < 7.60e9


def test_the_paged_leaves_are_the_published_cuts_own():
    """A page of each kind at the published widths: 2 full layers and 6
    window layers, 4 KV heads of 128, 16 tokens."""
    with open(CONFIG) as f:
        file = json.load(f)
    cfg = load(os.path.join(BENCH, "families", "swa_gqa_softmax_moe.py"),
               "adapter_swa_softmax2").program_config(file)
    assert sm.PAGED_FAMILY.leaf_shapes(cfg, 16) == (
        (2, 1, 4, 16, 128),) * 2 + ((6, 1, 4, 16, 128),) * 2
    assert cfg.window_pages(16) == 64
