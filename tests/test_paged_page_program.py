"""The dense page program (``kv_paging.paged_decode_page_jit``) takes a
page's tokens through each layer TOGETHER. Held here to the program it
replaced a loop of (``paged_decode_step_jit``, a token at a time from an
empty tail), to the dense family's plain reference
(``benchmark/references/dense_gqa.py``), and to its own structure: no loop
over tokens, each weight in one matmul. CPU, float32.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import llama
from oncilla_tpu.models.kv_paging import (
    paged_decode_page_jit,
    paged_decode_step_jit,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = llama.LlamaConfig.tiny()
P = 4  # page_tokens
# None, smaller than a page, between one page and the longest context.
WINDOWS = (None, 3, 6)


@pytest.fixture(scope="module")
def params():
    return llama.init_params_host(7, CFG)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "reference_dense_gqa",
        os.path.join(ROOT, "benchmark", "references", "dense_gqa.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def leaf(rng, batch, tokens, scale=1.0):
    shape = (CFG.n_layers, batch, CFG.n_kv_heads, tokens, CFG.head_dim)
    return jnp.asarray(rng.standard_normal(shape, dtype=np.float32) * scale)


@pytest.mark.parametrize(
    "pages,window,ctx_start,batch",
    list(itertools.product((0, 1, 3), WINDOWS, (0, 8), (1, 2))))
def test_page_equals_p_steps_from_an_empty_tail(params, rng, pages, window,
                                                ctx_start, batch):
    """Logits and returned tails equal P calls of the per-token step, which
    starts from an empty tail (``tail_len`` 0 masks whatever the buffers
    hold: both sides are handed the same garbage)."""
    cfg = dataclasses.replace(CFG, window=window)
    C = pages * P
    pos0 = ctx_start + C
    k_ctx, v_ctx = leaf(rng, batch, C), leaf(rng, batch, C)
    garbage_k, garbage_v = leaf(rng, batch, P, 9.0), leaf(rng, batch, P, 9.0)
    tokens = jnp.asarray(
        rng.integers(0, CFG.vocab, size=(batch, P), dtype=np.int32))

    tk, tv = jnp.array(garbage_k), jnp.array(garbage_v)
    want = []
    for j in range(P):
        meta = jnp.asarray([pos0 + j, j, ctx_start], jnp.int32)
        lg, tk, tv = paged_decode_step_jit(
            params, tokens[:, j], meta, k_ctx, v_ctx, tk, tv, cfg)
        want.append(np.asarray(lg))
    want = np.stack(want, axis=1)  # (B, P, V)

    got, gk, gv = paged_decode_page_jit(
        params, tokens, jnp.asarray([pos0, ctx_start], jnp.int32),
        k_ctx, v_ctx, jnp.array(garbage_k), jnp.array(garbage_v), cfg)
    assert got.shape == (batch, P, CFG.vocab) and got.dtype == jnp.float32
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(tk), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(tv), atol=1e-4)


@pytest.mark.parametrize(
    "window,evict,batch",
    [(w, e, b) for w, e in ((None, False), (3, False), (3, True),
                            (6, False), (6, True)) for b in (1, 2)])
def test_pages_equal_the_plain_reference(params, reference, rng, window,
                                         evict, batch):
    """A prompt of four pages through the page program, each page's tails
    the next one's context: every position's logits are the plain
    reference's on the same prompt, at the tolerance the dense engine cases
    hold (``test_serving_batched.py::held_to_reference``). With ``evict`` a
    page that no later query's window reaches is dropped from the context,
    so ``ctx_start`` moves as it does under ``BucketedPagedDecoder``."""
    cfg = dataclasses.replace(CFG, window=window)
    conf = {"num_attention_heads": CFG.n_heads,
            "num_key_value_heads": CFG.n_kv_heads,
            "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.norm_eps,
            "sliding_window": window}
    n_pages = 4
    tokens = rng.integers(0, CFG.vocab, size=(batch, n_pages * P),
                          dtype=np.int32)
    k_ctx, v_ctx = leaf(rng, batch, 0), leaf(rng, batch, 0)
    ctx_start, got = 0, []
    for p in range(n_pages):
        pos0 = p * P
        if evict:
            while k_ctx.shape[3] and ctx_start + P <= pos0 - window:
                k_ctx, v_ctx = k_ctx[:, :, :, P:], v_ctx[:, :, :, P:]
                ctx_start += P
        logits, tk, tv = paged_decode_page_jit(
            params, jnp.asarray(tokens[:, pos0:pos0 + P]),
            jnp.asarray([pos0, ctx_start], jnp.int32), k_ctx, v_ctx,
            leaf(rng, batch, P), leaf(rng, batch, P), cfg)
        got.append(np.asarray(logits))
        k_ctx = jnp.concatenate([k_ctx, tk], axis=3)
        v_ctx = jnp.concatenate([v_ctx, tv], axis=3)
    assert bool(evict) == bool(ctx_start)
    want = reference.logits_at(params, tokens, np.arange(n_pages * P), conf)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=1e-4)


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


@pytest.mark.parametrize("pages,window", [(0, None), (2, None), (2, 3)])
def test_no_loop_over_tokens_and_each_weight_in_one_matmul(pages, window):
    """The mechanism itself: the traced program holds no ``scan`` or
    ``while``, and every weight matrix enters exactly one ``dot_general``
    (seven a layer and the head), beside a layer's two attention products.
    A per-token loop, rolled or unrolled, fails one of the two."""
    cfg = dataclasses.replace(CFG, window=window)
    shapes = jax.eval_shape(
        lambda k: llama.init_params(k, cfg), jax.random.key(0))
    f32 = jnp.float32
    ctx = jax.ShapeDtypeStruct(
        (cfg.n_layers, 1, cfg.n_kv_heads, pages * P, cfg.head_dim), f32)
    tail = jax.ShapeDtypeStruct(
        (cfg.n_layers, 1, cfg.n_kv_heads, P, cfg.head_dim), f32)
    closed = jax.make_jaxpr(
        lambda *a: paged_decode_page_jit(*a, cfg))(
        shapes, jax.ShapeDtypeStruct((1, P), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), ctx, ctx, tail, tail)
    eqns = list(equations(closed.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert not names & {"scan", "while"}, names

    matrices = [k for k in llama.LAYER_KEYS if shapes[k].ndim == 3]
    assert len(matrices) == 7
    weight_shapes = {shapes[k].shape[1:] for k in matrices}
    weight_shapes.add(shapes["lm_head"].shape)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    over_weight = [e for e in dots
                   if any(v.aval.shape in weight_shapes for v in e.invars)]
    assert len(over_weight) == 7 * cfg.n_layers + 1
    assert len(dots) == len(over_weight) + 2 * cfg.n_layers
    # Each of them multiplies the whole page: P rows, not one.
    for e in over_weight:
        act = next(v for v in e.invars if v.aval.shape not in weight_shapes)
        assert P in act.aval.shape, act.aval.shape
