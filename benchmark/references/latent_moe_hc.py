"""The plain reference of the ``latent_moe_hc`` family
(``families/latent_moe_hc.py`` names it): a decoder whose attention caches a
latent (multi-head latent attention), whose feed-forward layers after the
leading dense ones are sigmoid-routed experts beside a shared expert, and
whose residual path is ``hc_mult`` streams mixed by manifold-constrained
hyper-connections (arXiv:2512.24880 over arXiv:2409.19606). Straightforward
``jax.numpy`` and float32, no cache, no pages, no batching tricks, nothing
imported from the program; every expert is computed for every token and
weighted by a mask, so no routing code is shared with the program either.

Per token, with ``n = hc_mult`` streams ``X`` of ``(n, D)`` (the embedding
copied into each; summed before the final norm):

- every sub-layer ``F`` (attention, then FFN or experts) has a gain over
  ``vec(X)``, ``phi`` of ``(nD, 2n + n*n)`` (columns: pre, post, res), a
  bias of the same width and three scalar gates ``alpha``:
  ``x~ = RMSNorm(vec(X))``; ``Hpre = sigmoid(a0 * x~ phi_pre + b_pre)``;
  ``Hpost = 2 sigmoid(a1 * x~ phi_post + b_post)``;
  ``Hres = SK(clamp(a2 * mat(x~ phi_res) + b_res))``, ``SK`` exponentiating
  and then normalising rows, then columns (denominator plus ``hc_eps``),
  ``hc_sinkhorn_iters`` times; ``y = F(RMSNorm(Hpre X))``;
  ``X' = Hres X + outer(Hpost, y)``.
- attention: ``cq = RMSNorm(x Wqa)``; ``q = cq Wqb``, per head
  ``[q_nope | q_rope]``; ``[ckv | k_rope] = x Wkva``; ``ckv = RMSNorm(ckv)``;
  ``k_rope`` rotated and shared by all heads; per head
  ``[k_nope | v] = ckv Wkvb``; scores
  ``(q_nope.k_nope + q_rope.k_rope) * (dn + dr)^-0.5 * m^2``,
  ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; rotary frequencies blended
  as YaRN states; causal softmax; output through ``Wo``.
- experts: ``s = sigmoid(x Wr)``; the ``num_experts_per_tok`` largest of
  ``s + e_score_correction_bias``; weights ``s_i / sum(s_chosen)`` times
  ``routed_scaling_factor``; ``sum w_i SwiGLU_i(x)`` plus the shared SwiGLU.
  No token is dropped. A dense layer is one SwiGLU.

Departures, each in the configuration file under ``assumed``: rotary pairs
are adjacent (x[2i], x[2i+1]); group-limited routing is left out
(``n_group`` 1); the multi-token-prediction head is not part of greedy
decoding.

The weights come from the program (its input, a dict of stacked leaves; the
names are in ``_layer_leaves``) in whatever type it serves them; one layer
is cast to float32 at a time and an expert layer in blocks of experts, so
that it runs beside 9.6 GB of bf16 weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ATTN_LEAVES = ("ln_attn", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
               "wkv_b", "wo")
HC_LEAVES = ("hc_norm", "hc_phi", "hc_b", "hc_alpha")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTER_LEAVES = ("w_router", "e_bias")
EXPERT_LEAVES = ("w_gate_e", "w_up_e", "w_down_e")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")
# Experts cast to float32 at a time: 8 x 11 M parameters are 352 MB.
EXPERT_BLOCK = 8
# Vocabulary columns of the head cast at a time.
HEAD_BLOCK = 32768


def dims_of(conf: dict) -> tuple:
    """What the equations need of a configuration file's published keys;
    hashable, so it can be a static argument."""
    rs = conf["rope_scaling"]
    return (
        int(conf["num_attention_heads"]), int(conf["qk_nope_head_dim"]),
        int(conf["qk_rope_head_dim"]), int(conf["v_head_dim"]),
        int(conf["kv_lora_rank"]), float(conf["rope_theta"]),
        float(rs["factor"]), float(rs["beta_fast"]), float(rs["beta_slow"]),
        int(rs["original_max_position_embeddings"]),
        float(rs["mscale"]), float(rs["mscale_all_dim"]),
        float(conf["rms_norm_eps"]), int(conf["hc_mult"]),
        int(conf["hc_sinkhorn_iters"]), float(conf["hc_eps"]),
        float(conf["mhc_h_res_clamp_min"]), float(conf["mhc_h_res_clamp_max"]),
        int(conf["num_experts_per_tok"]), float(conf["routed_scaling_factor"]),
    )


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _yarn_inv_freq(dr, theta, factor, beta_fast, beta_slow, original):
    """YaRN's blend of the plain and the interpolated rotary frequencies."""
    exponent = np.arange(0, dr, 2, dtype=np.float64) / dr
    extra = 1.0 / theta ** exponent
    inter = 1.0 / (factor * theta ** exponent)

    def correction_dim(rotations):
        return dr * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return jnp.asarray(inter * (1 - keep) + extra * keep, jnp.float32)


def _rope(x, inv_freq):
    """x: (B, S, ..., dr). Rotates each adjacent pair by position * freq."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def sinkhorn(logits, iters, eps, lo, hi):
    """exp of the clamped logits, then rows and columns normalised in turn."""
    m = jnp.exp(jnp.clip(logits, lo, hi))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def _hc_coefficients(X, hc, dims):
    """X: (B, S, n, D) -> Hpre (B, S, n), Hpost (B, S, n), Hres (B, S, n, n)."""
    eps, n, iters, hc_eps, lo, hi = dims[12:18]
    B, S = X.shape[:2]
    xt = _rmsnorm(X.reshape(B, S, -1), hc["hc_norm"], eps)
    z = xt @ hc["hc_phi"]
    a, b = hc["hc_alpha"], hc["hc_b"]
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = sinkhorn(a[2] * z[..., 2 * n:].reshape(B, S, n, n)
                   + b[2 * n:].reshape(n, n), iters, hc_eps, lo, hi)
    return pre, post, res


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("dims",))
def _mix_in(X, hc, ln, dims):
    """The sub-layer's input and what the way out needs."""
    hc, ln = _f32(hc), ln.astype(jnp.float32)
    pre, post, res = _hc_coefficients(X, hc, dims)
    h = _rmsnorm(jnp.einsum("bsn,bsnd->bsd", pre, X), ln, dims[12])
    return h, post, res


@jax.jit
def _mix_out(X, y, post, res):
    return (jnp.einsum("bsij,bsjd->bsid", res, X)
            + post[..., None] * y[:, :, None, :])


@functools.partial(jax.jit, static_argnames=("dims",))
def _attention(h, lp, dims):
    (H, dn, dr, dv, R, theta, factor, beta_fast, beta_slow, original,
     _mscale, mscale_all, eps) = dims[:13]
    lp = _f32(lp)
    B, S, _ = h.shape
    inv_freq = _yarn_inv_freq(dr, theta, factor, beta_fast, beta_slow,
                              original)
    cq = _rmsnorm(h @ lp["wq_a"], lp["q_norm"], eps)
    q = (cq @ lp["wq_b"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv_freq)
    kva = h @ lp["wkv_a"]
    ckv = _rmsnorm(kva[..., :R], lp["kv_norm"], eps)
    k_rope = _rope(kva[..., R:], inv_freq)
    kv = (ckv @ lp["wkv_b"]).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    m = 0.1 * mscale_all * math.log(factor) + 1.0 if factor > 1 else 1.0
    scale = (dn + dr) ** -0.5 * m * m
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * dv)
    return a @ lp["wo"]


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@functools.partial(jax.jit, static_argnames=("dims",))
def _route(h, w_router, e_bias, dims):
    """(B, S, E) weights, zero off the chosen experts, and the chosen ids
    in ascending order. The largest is taken k times, each time masked out:
    no top-k call shared with the program."""
    k, scaling = dims[18], dims[19]
    s = jax.nn.sigmoid(h @ w_router.astype(jnp.float32))
    left = s + e_bias.astype(jnp.float32)
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)
        hit = jax.nn.one_hot(best, s.shape[-1], dtype=bool)
        chosen |= hit
        left = jnp.where(hit, -jnp.inf, left)
    picked = jnp.where(chosen, s, 0.0)
    weights = scaling * picked / picked.sum(-1, keepdims=True)
    ids = jnp.sort(jnp.where(chosen, jnp.arange(s.shape[-1]), s.shape[-1]),
                   axis=-1)[..., :k]
    return weights, ids


@jax.jit
def _expert_block(h, w_gate, w_up, w_down, weights):
    """Every expert of the block over every token, weighted by the mask."""
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    act = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, w_gate))
           * jnp.einsum("bsd,edf->bsef", h, w_up))
    y = jnp.einsum("bsef,efd->bsed", act, w_down)
    return jnp.einsum("bse,bsed->bsd", weights, y)


def _experts(h, ep, dims):
    weights, ids = _route(h, ep["w_router"], ep["e_bias"], dims)
    y = _swiglu(h, ep["ws_gate"], ep["ws_up"], ep["ws_down"])
    E = ep["w_gate_e"].shape[0]
    for e0 in range(0, E, EXPERT_BLOCK):
        e1 = min(e0 + EXPERT_BLOCK, E)
        y = y + _expert_block(h, ep["w_gate_e"][e0:e1], ep["w_up_e"][e0:e1],
                              ep["w_down_e"][e0:e1], weights[..., e0:e1])
    return y, ids


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, ln_out, lm_head, eps):
    return _rmsnorm(x, ln_out.astype(jnp.float32), eps) @ lm_head.astype(
        jnp.float32)


def _forward(params: dict, tokens, rows, conf: dict):
    dims = dims_of(conf)
    n = dims[13]
    tokens = jnp.asarray(tokens, jnp.int32)
    n_layers = params["wq_a"].shape[0]
    n_dense = params["w_gate"].shape[0]
    routed = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        X = jnp.repeat(x[:, :, None, :], n, axis=2)
        for i in range(n_layers):
            for sub in range(2):
                hc = {k: params[k][i, sub] for k in HC_LEAVES}
                ln = params["ln_attn" if sub == 0 else "ln_mlp"][i]
                h, post, res = _mix_in(X, hc, ln, dims)
                if sub == 0:
                    y = _attention(
                        h, {k: params[k][i] for k in ATTN_LEAVES[1:]}, dims)
                elif i < n_dense:
                    y = _swiglu(h, *(params[k][i] for k in DENSE_LEAVES))
                else:
                    j = i - n_dense
                    y, ids = _experts(
                        h, {k: params[k][j] for k in ROUTER_LEAVES
                            + EXPERT_LEAVES + SHARED_LEAVES}, dims)
                    routed.append(ids)
                X = _mix_out(X, y, post, res)
        x = X.sum(axis=2)[:, jnp.asarray(rows)]
        V = params["lm_head"].shape[1]
        out = np.concatenate([
            np.asarray(_head_block(x, params["ln_out"],
                                   params["lm_head"][:, v0:v0 + HEAD_BLOCK],
                                   dims[12]))
            for v0 in range(0, V, HEAD_BLOCK)], axis=-1)
    return out, routed


def logits_at(params: dict, tokens, rows, conf: dict) -> np.ndarray:
    """Float32 logits of ``tokens`` (B, S) at positions ``rows`` (R,):
    (B, R, V). Every position attends causally to what precedes it."""
    return _forward(params, tokens, rows, conf)[0]


def experts_at(params: dict, tokens, conf: dict) -> np.ndarray:
    """The experts each position chose in each expert layer, ascending:
    (expert layers, B, S, num_experts_per_tok). For the tests that hold the
    program's routing to this one."""
    S = np.asarray(tokens).shape[1]
    return np.stack([np.asarray(r) for r in
                     _forward(params, tokens, np.arange(S), conf)[1]])
