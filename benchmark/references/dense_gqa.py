"""The plain reference of the ``dense_gqa`` family (``families/dense_gqa.py``
names it): the decoder's forward pass in straightforward
``jax.numpy`` and float32, with no cache, no pages, no batching tricks and
nothing imported from the program. It follows the published description of
the InternLM2 / Mistral decoder (pre-norm, RMSNorm, rotary positions on q
and k, grouped-query attention, causal mask with an optional sliding window,
SwiGLU, untied output head). Departures, each noted in the configuration
files under ``assumed``: rotary pairs are adjacent (x[2i], x[2i+1]) as the
program lays them out, not split halves; q, k and v are three matrices.

The weights come from the program (they are its input, a dict of stacked
leaves: embed (V, D), wq/wk/wv/wo, w_gate/w_up/w_down, ln_attn/ln_mlp with a
leading layer axis, ln_out (D,), lm_head (D, V)), in whatever type it serves
them; the reference casts ONE layer to float32 at a time, so that it runs
beside 7.5 GB of bf16 weights. A TPU multiplies float32 matrices in bf16
passes unless told otherwise, hence ``default_matmul_precision("highest")``.

A family's reference offers ``logits_at(params, tokens, rows, conf)``, with
``conf`` the configuration file as read; which of its keys matter is this
file's business (``dims_of``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "ln_attn", "ln_mlp")


def dims_of(conf: dict) -> tuple:
    """(heads, kv heads, rope theta, norm eps, window) from a configuration
    file's published keys; hashable, so it can be a static argument."""
    return (int(conf["num_attention_heads"]), int(conf["num_key_value_heads"]),
            float(conf["rope_theta"]), float(conf["rms_norm_eps"]),
            conf.get("sliding_window"))


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, H, Hd). Rotates each adjacent pair by position * freq."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims",))
def _layer(x, lp, dims):
    H, KV, theta, eps, window = dims
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    B, S, D = x.shape
    hd = D // H
    h = _rmsnorm(x, lp["ln_attn"], eps)
    q = _rope((h @ lp["wq"]).reshape(B, S, H, hd), theta)
    k = _rope((h @ lp["wk"]).reshape(B, S, KV, hd), theta)
    v = (h @ lp["wv"]).reshape(B, S, KV, hd)
    # Query head h reads key/value head h // (H / KV).
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if window is not None:
        mask &= j > i - window
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * hd)
    x = x + a @ lp["wo"]
    h = _rmsnorm(x, lp["ln_mlp"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_out, lm_head, eps):
    return _rmsnorm(x, ln_out.astype(jnp.float32), eps) @ lm_head.astype(
        jnp.float32)


def logits_at(params: dict, tokens, rows, conf: dict) -> np.ndarray:
    """Float32 logits of ``tokens`` (B, S) at positions ``rows`` (R,):
    (B, R, V). Every position attends causally to what precedes it."""
    dims = dims_of(conf)
    tokens = jnp.asarray(tokens, jnp.int32)
    n_layers = params["wq"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(n_layers):
            x = _layer(x, {k: params[k][i] for k in LAYER_LEAVES}, dims)
        x = x[:, jnp.asarray(rows)]
        out = _head(x, params["ln_out"], params["lm_head"], dims[3])
    return np.asarray(out)
