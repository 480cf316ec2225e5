"""The plain reference of the ``conv_gqa_moe`` family
(``families/conv_gqa_moe.py`` names it): a pre-norm decoder whose layers are
gated short convolutions with grouped-query attention layers among them, and
whose feed-forward layers after the leading dense ones are sigmoid-scored
experts with no shared expert. Straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision; a whole sequence at once: no cache, no pages,
no carry, no chunks; nothing imported from the program; every expert is
computed for every token and weighted by a mask, so no routing code is
shared with the program.

With ``D = hidden_size``, ``H = num_attention_heads``, ``KV =
num_key_value_heads``, ``hd = head_dim``, ``K = conv_L_cache``, ``eps =
norm_eps``, layer ``i`` of type ``layer_types[i]`` computes, per token ``t``
of a sequence,

    u = RMSNorm_op(x);  x += ShortConv(u) or Attn(u)
    x += FFN_i(RMSNorm_ffn(x))

- ``ShortConv``: ``[B, C, z] = u W_in`` cut in thirds in this order; ``v_t =
  B_t * z_t``; ``c_t = sum_j w[j] * v_{t-(K-1)+j}`` over ``j = 0..K-1``, a
  depthwise causal convolution with ``v`` zero before the sequence's start,
  here a sum of shifted copies of the zero-padded sequence; ``y_t = (C_t *
  c_t) W_out``. No bias.
- ``Attn``: ``q = u W_q`` as (H, hd), ``k = u W_k`` and ``v = u W_v`` as (KV,
  hd); an RMS norm over ``hd`` with a learned weight on every head of q and
  of k; rotary by position over the whole head, adjacent pairs ``(x[2i],
  x[2i+1])``, frequencies ``rope_theta ** (-2i / hd)``; scores ``q k^T /
  sqrt(hd)``, query head ``h`` against KV head ``h // (H / KV)``, causal;
  softmax; ``o`` the weighted ``v``; ``x += concat(o) W_o``.
- ``FFN_i``: one SwiGLU of ``intermediate_size`` for ``i <
  num_dense_layers``; else ``s = sigmoid(h W_r)`` over the
  ``num_experts`` outputs, the ``num_experts_per_tok`` largest of ``s +
  bias`` chosen, weights ``s_i / (sum(s_chosen) + 1e-6)`` times
  ``routed_scaling_factor``, ``y = sum_i w_i SwiGLU_i(h)``.
- final RMS norm, then the head, which is the embedding transposed.

Departures and readings, each in the configuration file under ``assumed``:
the head's size, the tied head, where the q/k norms sit, the rotary
pairing, the order of ``in_proj``'s thirds, the ``1e-6``.

The weights come from the program (its input, a dict of stacked leaves:
``conv_*`` over the convolution layers, ``wq`` ... ``wo`` and the two norms
over the attention layers, expert leaves over the expert layers) in whatever
type it serves them; one layer is cast to float32 at a time, an expert layer
in blocks of experts, and attention is taken a block of queries at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CONV_LEAVES = ("conv_in", "conv_k", "conv_out")
ATTN_LEAVES = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTER_LEAVES = ("w_router", "e_bias")
EXPERT_LEAVES = ("w_gate_e", "w_up_e", "w_down_e")
# The constant in the chosen scores' normalisation (``assumed``).
NORM_TOPK_EPS = 1e-6
# Experts cast to float32 at a time: 8 x 9.4 M parameters are 302 MB.
EXPERT_BLOCK = 8
# Vocabulary rows of the tied head cast at a time.
HEAD_BLOCK = 32768
# Queries attended at a time: 32 heads x 512 x 2560 scores are 168 MB.
QUERY_BLOCK = 512
# A sequence is run at its length rounded up to a multiple of this, and so is
# the number of rows asked for. Every position looks back only (attention
# causally, the convolution over the K - 1 positions before it), so what is
# appended after a sequence's end changes nothing before it; a length of its
# own would compile every function here anew for every request compared
# (``kda_latent_moe.py::SEQ_BLOCK``).
SEQ_BLOCK = 512


def head_dim(conf: dict) -> int:
    return int(conf.get("head_dim")
               or conf["hidden_size"] // conf["num_attention_heads"])


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return _rmsnorm(x, w.astype(jnp.float32), eps)


@jax.jit
def _short_conv(u, lp):
    """u: (B, S, D) float32; ``lp`` the layer's three leaves."""
    lp = _f32(lp)
    S = u.shape[1]
    b, c, z = jnp.split(u @ lp["conv_in"], 3, axis=-1)
    v = b * z
    taps = lp["conv_k"]                       # (K, D): tap j weighs v_{t-(K-1)+j}
    K = taps.shape[0]
    padded = jnp.pad(v, ((0, 0), (K - 1, 0), (0, 0)))
    mixed = sum(taps[j] * padded[:, j:j + S] for j in range(K))
    return (c * mixed) @ lp["conv_out"]


def _rope(x, theta: float):
    """x: (B, S, heads, hd), position ``s`` at index s; adjacent pairs."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * freqs[None, :])[None, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit,
                   static_argnames=("kv_heads", "hd", "theta", "eps"))
def _project(u, lp, kv_heads, hd, theta, eps):
    """u: (B, S, D) -> normed, rotated q (B, S, H, hd), normed, rotated k
    repeated to the query heads, v likewise."""
    lp = _f32(lp)
    B, S, _ = u.shape
    q = (u @ lp["wq"]).reshape(B, S, -1, hd)
    k = (u @ lp["wk"]).reshape(B, S, kv_heads, hd)
    v = (u @ lp["wv"]).reshape(B, S, kv_heads, hd)
    q = _rope(_rmsnorm(q, lp["q_norm"], eps), theta)
    k = _rope(_rmsnorm(k, lp["k_norm"], eps), theta)
    group = q.shape[2] // kv_heads
    return q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


@jax.jit
def _attend(q, k, v, first):
    """A block of queries, the first at position ``first``, over every key.
    q: (B, Q, H, hd); k, v: (B, S, H, hd)."""
    i = first + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@jax.jit
def _out(o, wo):
    B, S, H, hd = o.shape
    return o.reshape(B, S, H * hd) @ wo.astype(jnp.float32)


def attention_layer(u, lp: dict, conf: dict):
    """One attention layer: u (B, S, D) float32, ``lp`` its six leaves."""
    q, k, v = _project(
        u, {n: lp[n] for n in ATTN_LEAVES[:5]},
        int(conf["num_key_value_heads"]), head_dim(conf),
        float(conf["rope_parameters"]["rope_theta"]), float(conf["norm_eps"]))
    S = u.shape[1]
    o = jnp.concatenate(
        [_attend(q[:, s:s + QUERY_BLOCK], k, v, s)
         for s in range(0, S, QUERY_BLOCK)], axis=1)
    return _out(o, lp["wo"])


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _largest(left, n: int):
    """The n largest along the last axis as a mask, the largest taken n
    times and masked out each time: no top-k call shared with the program."""
    chosen = jnp.zeros(left.shape, bool)
    for _ in range(n):
        hit = jax.nn.one_hot(jnp.argmax(left, axis=-1), left.shape[-1],
                             dtype=bool)
        chosen |= hit
        left = jnp.where(hit, -jnp.inf, left)
    return chosen


@functools.partial(jax.jit, static_argnames=("k", "scaling"))
def _route(h, w_router, e_bias, k, scaling):
    """(B, S, E) weights over the experts, zero off the chosen ones, and the
    chosen ids in ascending order."""
    s = jax.nn.sigmoid(h @ w_router.astype(jnp.float32))
    E = s.shape[-1]
    chosen = _largest(s + e_bias.astype(jnp.float32), k)
    picked = jnp.where(chosen, s, 0.0)
    weights = scaling * picked / (picked.sum(-1, keepdims=True)
                                  + NORM_TOPK_EPS)
    ids = jnp.sort(jnp.where(chosen, jnp.arange(E), E), axis=-1)[..., :k]
    return weights, ids


@jax.jit
def _expert_block(h, w_gate, w_up, w_down, weights):
    """Every expert of the block over every token, weighted by the mask."""
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    act = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, w_gate))
           * jnp.einsum("bsd,edf->bsef", h, w_up))
    y = jnp.einsum("bsef,efd->bsed", act, w_down)
    return jnp.einsum("bse,bsed->bsd", weights, y)


def expert_layer(h, ep: dict, conf: dict):
    """One expert layer: h (B, S, D) float32, ``ep`` its five leaves.
    Returns (the chosen experts' weighted sum, the chosen ids (B, S, k)
    ascending)."""
    weights, ids = _route(h, ep["w_router"], ep["e_bias"],
                          int(conf["num_experts_per_tok"]),
                          float(conf["routed_scaling_factor"]))
    y = jnp.zeros_like(h)
    for e0 in range(0, ep["w_gate_e"].shape[0], EXPERT_BLOCK):
        e1 = e0 + EXPERT_BLOCK
        y = y + _expert_block(
            h, ep["w_gate_e"][e0:e1], ep["w_up_e"][e0:e1],
            ep["w_down_e"][e0:e1], weights[..., e0:e1])
    return y, ids


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, ln_out, embed_rows, eps):
    return _rmsnorm(x, ln_out.astype(jnp.float32), eps) @ embed_rows.astype(
        jnp.float32).T


def _forward(params: dict, tokens, rows, conf: dict):
    eps = float(conf["norm_eps"])
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows)
    S, R = tokens.shape[1], rows.shape[0]
    tokens = jnp.asarray(np.pad(tokens, ((0, 0), (0, -S % SEQ_BLOCK))))
    rows = np.pad(rows, (0, -R % SEQ_BLOCK))
    n_layers = params["ln_op"].shape[0]
    n_dense = params["w_gate"].shape[0]
    if n_dense != conf["num_dense_layers"]:
        raise ValueError(f"{n_dense} dense layers in the weights, the "
                         f"configuration states {conf['num_dense_layers']}")
    routed = []
    seen = {"conv": 0, "full_attention": 0}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(n_layers):
            kind = conf["layer_types"][i]
            at = seen[kind]
            seen[kind] += 1
            u = _norm(x, params["ln_op"][i], eps)
            if kind == "conv":
                x = x + _short_conv(
                    u, {n: params[n][at] for n in CONV_LEAVES})
            else:
                x = x + attention_layer(
                    u, {n: params[n][at] for n in ATTN_LEAVES}, conf)
            h = _norm(x, params["ln_ffn"][i], eps)
            if i < n_dense:
                x = x + _swiglu(h, *(params[k][i] for k in DENSE_LEAVES))
            else:
                y, ids = expert_layer(
                    h, {k: params[k][i - n_dense]
                        for k in ROUTER_LEAVES + EXPERT_LEAVES}, conf)
                routed.append(ids)
                x = x + y
        x = x[:, jnp.asarray(rows)]
        V = params["embed"].shape[0]
        out = np.concatenate([
            np.asarray(_head_block(x, params["ln_out"],
                                   params["embed"][v0:v0 + HEAD_BLOCK], eps))
            for v0 in range(0, V, HEAD_BLOCK)], axis=-1)[:, :R]
    return out, routed


def logits_at(params: dict, tokens, rows, conf: dict) -> np.ndarray:
    """Float32 logits of ``tokens`` (B, S) at positions ``rows`` (R,):
    (B, R, V). Every position attends causally to what precedes it, and
    every convolution starts from zeros."""
    return _forward(params, tokens, rows, conf)[0]


def experts_at(params: dict, tokens, conf: dict) -> np.ndarray:
    """The experts each position chose in each expert layer, ascending:
    (expert layers, B, S, num_experts_per_tok). For the tests that hold the
    program's routing to this one."""
    S = np.asarray(tokens).shape[1]
    return np.stack([np.asarray(r)[:, :S] for r in
                     _forward(params, tokens, np.arange(S), conf)[1]])
