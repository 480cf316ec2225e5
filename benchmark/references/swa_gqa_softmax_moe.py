"""The plain reference of the ``swa_gqa_softmax_moe`` family
(``families/swa_gqa_softmax_moe.py`` names it; Mellum2): a pre-norm decoder
whose attention layers are grouped-query attention of two kinds with one
head count, full and sliding-window, and whose every feed-forward layer is
softmax-routed experts with no shared one. Straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision, no cache, no pages, no kinds of
page; nothing imported from the program; every expert is computed for every
token and weighted by a mask, so no routing code is shared with the program.

Per token ``t`` of a sequence, ``h = RMSNorm(x)`` (eps ``rms_norm_eps``)
before each sub-layer and ``x += F(h)`` after it. Every layer has ``H =
num_attention_heads`` query heads over ``num_key_value_heads`` KV heads of
``head_dim``:

- ``q = h Wq`` as (H, head_dim), ``k = h Wk`` and ``v = h Wv`` as (KV,
  head_dim), no bias. Rotary on q and k by position, adjacent pairs
  ``(x[2i], x[2i+1])`` of the whole head (``partial_rotary_factor`` 1 where
  the group states none). ``layer_types[i] == "full_attention"``: YaRN
  frequencies (``rope_theta``, ``factor``, ``original_max_position_
  embeddings``, ``beta_fast``, ``beta_slow``: plain frequencies where a
  dimension turns more than ``beta_fast`` times within the original length,
  frequencies divided by ``factor`` where fewer than ``beta_slow`` times, a
  linear ramp between) with cos and sin multiplied by ``attention_factor``.
  ``"sliding_attention"``: plain frequencies of its own ``rope_theta``.
- scores ``q k^T / sqrt(head_dim)``, query head ``h`` against KV head ``h //
  (H / KV)``; key ``j`` counts for query ``t`` when ``j <= t`` and, in a
  sliding layer, ``j > t - sliding_window``; softmax; ``x += concat(o) Wo``.
- experts: ``p = softmax(h Wr)`` over all ``num_experts`` router outputs;
  the ``num_experts_per_tok`` largest are chosen; weights ``p_i /
  sum(p_chosen)`` (``norm_topk_prob``), no bias, no scaling; ``sum over
  chosen w_i SwiGLU_i(h)``. Final norm, then the untied head.

Departures and readings, each in the configuration file under ``assumed``:
no q/k norm (the config has no key for one); ``layer_types`` decides which
layers use the window (``use_sliding_window`` and ``max_window_layers`` are
not read); the router in float32 on the float32 hidden; the rotary layout
(adjacent pairs); no MTP head (the config has no key for one, and greedy
decoding reads none).

The weights come from the program (its input, a dict of stacked leaves:
``f_*`` over the full layers, ``w_*`` over the sliding ones) in whatever
type it serves them; one layer is cast to float32 at a time, an expert
layer in blocks of experts, and attention is taken a block of queries at a
time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ATTN_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("w_gate_e", "w_up_e", "w_down_e")
# Experts cast to float32 at a time: 8 x 6.19 M parameters are 198 MB.
EXPERT_BLOCK = 8
# Vocabulary columns of the head cast at a time.
HEAD_BLOCK = 32768
# Queries attended at a time: 32 heads x 256 x 3072 scores are 101 MB.
QUERY_BLOCK = 256
# A sequence is run at its length rounded up to a multiple of this, and so is
# the number of rows asked for: every position attends causally, so what is
# appended after a sequence's end changes nothing before it, and one length
# a block compiles once for every request compared.
SEQ_BLOCK = 512


def rope_of(conf: dict, layer_type: str) -> tuple:
    """(rotated width, the factor on cos and sin, the frequencies as a
    tuple): hashable, so it can be a static argument."""
    rp = conf["rope_parameters"][layer_type]
    width = int(conf["head_dim"] * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    plain = [theta ** -(2 * j / width) for j in range(width // 2)]
    if rp.get("rope_type", "default") != "yarn":
        return width, 1.0, tuple(plain)
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def dim_turning(times):
        """The dimension that turns ``times`` times within ``orig``."""
        return width * math.log(orig / (times * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_turning(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_turning(rp["beta_slow"])), width - 1)
    freqs = []
    for j, f in enumerate(plain):
        keep = 1.0 - min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        freqs.append(f * keep + f / factor * (1.0 - keep))
    return width, float(rp["attention_factor"]), tuple(freqs)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def _rope(x, rope):
    """x: (B, S, heads, head_dim), position ``s`` at index s."""
    width, factor, freqs = rope
    S = x.shape[1]
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.asarray(freqs, jnp.float32)[None, :])[None, :, None, :]
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    x1, x2 = x[..., 0:width:2], x[..., 1:width:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (width,)), x[..., width:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("kv_heads", "head_dim", "rope"))
def _project(h, lp, kv_heads, head_dim, rope):
    """h: (B, S, D) -> rotated q (B, S, H, hd), rotated k repeated to the
    query heads, v likewise."""
    lp = _f32(lp)
    B, S, _ = h.shape
    q = (h @ lp["wq"]).reshape(B, S, -1, head_dim)
    k = (h @ lp["wk"]).reshape(B, S, kv_heads, head_dim)
    v = (h @ lp["wv"]).reshape(B, S, kv_heads, head_dim)
    group = q.shape[2] // kv_heads
    return (_rope(q, rope), jnp.repeat(_rope(k, rope), group, axis=2),
            jnp.repeat(v, group, axis=2))


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, first, window):
    """A block of queries, the first at position ``first``, over every key.
    q: (B, Q, H, hd); k, v: (B, S, H, hd)."""
    i = first + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@jax.jit
def _out(o, wo):
    B, S, H, hd = o.shape
    return o.reshape(B, S, H * hd) @ wo.astype(jnp.float32)


def attention_layer(h, lp: dict, conf: dict, layer_type: str):
    """One attention layer: h (B, S, D) float32, ``lp`` its four leaves."""
    window = (int(conf["sliding_window"])
              if layer_type == "sliding_attention" else None)
    with jax.default_matmul_precision("highest"):
        q, k, v = _project(
            h, {n: lp[n] for n in ATTN_LEAVES[:3]},
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            rope_of(conf, layer_type))
        S = h.shape[1]
        o = jnp.concatenate(
            [_attend(q[:, s:s + QUERY_BLOCK], k, v, s, window)
             for s in range(0, S, QUERY_BLOCK)], axis=1)
        return _out(o, lp["wo"])


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


@functools.partial(jax.jit, static_argnames=("k",))
def _route(h, w_router, k):
    """(B, S, E) weights over every expert, zero off the chosen ones, and
    the chosen ids in ascending order."""
    p = jax.nn.softmax(h @ w_router.astype(jnp.float32), axis=-1)
    E = p.shape[-1]
    chosen = _largest(p, k)
    picked = jnp.where(chosen, p, 0.0)
    weights = picked / picked.sum(-1, keepdims=True)
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
    """One expert layer: h (B, S, D) float32, ``ep`` its leaves (the
    router and every expert). Returns (the experts' sum, the chosen ids
    (B, S, k) ascending)."""
    with jax.default_matmul_precision("highest"):
        weights, ids = _route(h, ep["w_router"],
                              int(conf["num_experts_per_tok"]))
        n = ep["w_gate_e"].shape[0]
        if n != weights.shape[-1]:
            raise ValueError(f"{n} experts held of a router over "
                             f"{weights.shape[-1]}: the family holds all")
        y = jnp.zeros(h.shape, jnp.float32)
        for e0 in range(0, n, EXPERT_BLOCK):
            e1 = min(e0 + EXPERT_BLOCK, n)
            y = y + _expert_block(
                h, ep["w_gate_e"][e0:e1], ep["w_up_e"][e0:e1],
                ep["w_down_e"][e0:e1], weights[..., e0:e1])
    return y, ids


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return _rmsnorm(x, w.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, ln_out, lm_head, eps):
    return _rmsnorm(x, ln_out.astype(jnp.float32), eps) @ lm_head.astype(
        jnp.float32)


def _forward(params: dict, tokens, rows, conf: dict):
    eps = float(conf["rms_norm_eps"])
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows)
    S, R = tokens.shape[1], rows.shape[0]
    tokens = jnp.asarray(np.pad(tokens, ((0, 0), (0, -S % SEQ_BLOCK))))
    rows = np.pad(rows, (0, -R % SEQ_BLOCK))
    n_layers = params["ln_attn"].shape[0]
    H, hd = int(conf["num_attention_heads"]), int(conf["head_dim"])
    routed = []
    seen = {"full_attention": 0, "sliding_attention": 0}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(n_layers):
            kind = conf["layer_types"][i]
            pre = "f_" if kind == "full_attention" else "w_"
            lp = {n: params[pre + n][seen[kind]] for n in ATTN_LEAVES}
            seen[kind] += 1
            if lp["wq"].shape[1] != H * hd:
                raise ValueError(f"layer {i}: Wq of {lp['wq'].shape[1]} "
                                 f"columns for {H} heads of {hd}")
            x = x + attention_layer(_norm(x, params["ln_attn"][i], eps), lp,
                                    conf, kind)
            h = _norm(x, params["ln_mlp"][i], eps)
            y, ids = expert_layer(
                h, {k: params[k][i]
                    for k in ("w_router",) + EXPERT_LEAVES}, conf)
            routed.append(ids)
            x = x + y
        x = x[:, jnp.asarray(rows)]
        V = params["lm_head"].shape[1]
        out = np.concatenate([
            np.asarray(_head_block(x, params["ln_out"],
                                   params["lm_head"][:, v0:v0 + HEAD_BLOCK],
                                   eps))
            for v0 in range(0, V, HEAD_BLOCK)], axis=-1)[:, :R]
    return out, routed


def logits_at(params: dict, tokens, rows, conf: dict) -> np.ndarray:
    """Float32 logits of ``tokens`` (B, S) at positions ``rows`` (R,):
    (B, R, V). Every position attends causally to what precedes it, a
    sliding layer to its last ``sliding_window`` positions."""
    return _forward(params, tokens, rows, conf)[0]


def experts_at(params: dict, tokens, conf: dict) -> np.ndarray:
    """The experts each position chose in each layer, ascending: (layers,
    B, S, num_experts_per_tok). For the tests that hold the program's
    routing to this one."""
    S = np.asarray(tokens).shape[1]
    return np.stack([np.asarray(r)[:, :S] for r in
                     _forward(params, tokens, np.arange(S), conf)[1]])
