"""The plain reference of the ``kda_latent_moe`` family
(``families/kda_latent_moe.py`` names it): a pre-norm decoder whose layer
``i`` attends by multi-head latent attention where ``(i + 1) %
layer_group_size == 0`` and by Kimi Delta Attention (arXiv:2510.26692)
elsewhere, and whose feed-forward layers after the leading dense ones are
sigmoid-scored experts chosen under a group limit beside one shared expert.
Straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision,
no cache, no pages, no carry handed between calls, no chunks; nothing
imported from the program; every held expert is computed for every token
and weighted by a mask, so no routing code is shared with the program.

Per token ``t`` of a sequence, ``h = RMSNorm(x)`` before each sub-layer
and ``x += F(h)`` after it:

- KDA, H heads of width ``head_dim``: ``[q~ | k~ | v~] = h Wqkv``; each
  channel convolved causally over time with its own
  ``short_conv_kernel_size`` taps (the last tap on the token itself, zeros
  before the sequence), then SiLU; ``q = q~ / |q~| * head_dim^-0.5``,
  ``k = k~ / |k~|`` a head (norms as ``sqrt(sum + 1e-6)``); log-decay a
  channel ``g = kda_lower_bound * sigmoid(exp(A_log) * (h Wf + dt_bias))``
  with ``A_log`` a head, ``a = exp(g)``; ``[b | gate] = sigmoid(h Wbg)`` a
  head; the state a head, from zeros:
  ``S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T``, ``o = S_t^T q``;
  the output ``(RMSNorm_head(o) * gate) Wo``. No positional code.
- latent attention: ``q = h Wq`` (``q_lora_rank`` null), per head
  ``[q_nope | q_rope]``; ``[ckv | k_rope] = h Wkva``; ``ckv = RMSNorm(ckv)``;
  ``k_rope`` rotated (theta ``rope_theta``, no scaling) and shared by all
  heads; per head ``[k_nope | v] = ckv Wkvb``; scores
  ``(q_nope.k_nope + q_rope.k_rope) * (dn + dr)^-0.5``; causal softmax;
  output through ``Wo``.
- experts: ``s = sigmoid(h Wr)`` over ALL the router's outputs; the choice
  is made on ``s + bias``: a group's score is the sum of its two largest,
  the ``topk_group`` best of ``n_group`` groups stay, the
  ``num_experts_per_tok`` largest among them are chosen; weights
  ``s_i / sum(s_chosen)`` times ``routed_scaling_factor``. Of the chosen,
  the experts ``first_expert .. first_expert + num_experts`` are HELD here
  (``w_*_e`` stack those and no others): ``sum over held chosen w_i
  SwiGLU_i(h)`` plus the shared SwiGLU. What the absent experts would have
  added is left out, and that partial result goes on. A dense layer is one
  SwiGLU.

Departures, each in the configuration file under ``assumed``: rotary pairs
are adjacent (x[2i], x[2i+1]); the form of the safe gate and the place of
the head-wise output gate; text only; no draft head.

The weights come from the program (its input, a dict of stacked leaves: KDA
leaves over the KDA layers, latent leaves over the latent layers, expert
leaves over the held experts) in whatever type it serves them; one layer
is cast to float32 at a time and an expert layer in blocks of experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KDA_LEAVES = ("kda_wqkv", "kda_conv", "kda_wf", "kda_A_log", "kda_dt_bias",
              "kda_wbg", "kda_o_norm", "kda_wo")
MLA_LEAVES = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTER_LEAVES = ("w_router", "e_bias")
EXPERT_LEAVES = ("w_gate_e", "w_up_e", "w_down_e")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")
# Experts cast to float32 at a time: 16 x 5.9 M parameters are 377 MB.
EXPERT_BLOCK = 16
# Vocabulary columns of the head cast at a time.
HEAD_BLOCK = 32768
# A sequence is run at its length rounded up to a multiple of this, and so is
# the number of rows asked for. Every position attends causally, so what is
# appended after a sequence's end changes nothing before it; a length of its
# own would compile every function here anew for every request compared
# (sixteen lengths in ``state-decode``, ten of them a run: 200 s of a run).
SEQ_BLOCK = 512


def dims_of(conf: dict) -> tuple:
    """What the equations need of a configuration file's published keys;
    hashable, so it can be a static argument."""
    return (
        int(conf["num_attention_heads"]), int(conf["head_dim"]),
        int(conf["qk_nope_head_dim"]), int(conf["qk_rope_head_dim"]),
        int(conf["v_head_dim"]), int(conf["kv_lora_rank"]),
        float(conf["rope_theta"]), float(conf["rms_norm_eps"]),
        float(conf["kda_lower_bound"]), int(conf["n_group"]),
        int(conf["topk_group"]), int(conf["num_experts_per_tok"]),
        float(conf["routed_scaling_factor"]),
    )


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("dims",))
def _kda(h, lp, dims):
    """h: (B, S, D) -> (B, S, D), the state from zeros, a token at a time."""
    H, dk = dims[0], dims[1]
    eps, lower = dims[7], dims[8]
    lp = _f32(lp)
    B, S, _ = h.shape
    taps = lp["kda_conv"].shape[0]
    x = h @ lp["kda_wqkv"]
    before = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = sum(lp["kda_conv"][j] * before[:, j:j + S] for j in range(taps))
    q, k, v = (a.reshape(B, S, H, dk)
               for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
    q = _unit(q) * dk ** -0.5
    k = _unit(k)
    f = (h @ lp["kda_wf"] + lp["kda_dt_bias"]).reshape(B, S, H, dk)
    a = jnp.exp(lower * jax.nn.sigmoid(
        jnp.exp(lp["kda_A_log"])[:, None] * f))
    bg = jax.nn.sigmoid(h @ lp["kda_wbg"])
    b, gate = bg[..., :H], bg[..., H:]

    def token(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp          # (B, H, dk) ..., b_t (B, H)
        state = a_t[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (b_t[..., None, None] * k_t[..., None]
                         * (v_t - seen)[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    time_first = [m.swapaxes(0, 1) for m in (q, k, v, a, b)]
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dk, dk), jnp.float32),
                        time_first)
    o = _rmsnorm(o.swapaxes(0, 1), lp["kda_o_norm"], eps) * gate[..., None]
    return o.reshape(B, S, H * dk) @ lp["kda_wo"]


def _rope(x, inv_freq):
    """x: (B, S, ..., dr). Rotates each adjacent pair by position * freq."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims",))
def _mla(h, lp, dims):
    H, _, dn, dr, dv, R, theta, eps = dims[:8]
    lp = _f32(lp)
    B, S, _ = h.shape
    inv_freq = jnp.asarray(
        1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr),
        jnp.float32)
    q = (h @ lp["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv_freq)
    kva = h @ lp["wkv_a"]
    ckv = _rmsnorm(kva[..., :R], lp["kv_norm"], eps)
    k_rope = _rope(kva[..., R:], inv_freq)
    kv = (ckv @ lp["wkv_b"]).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) * (dn + dr) ** -0.5
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * dv)
    return a @ lp["wo"]


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


@functools.partial(jax.jit, static_argnames=("dims",))
def _route(h, w_router, e_bias, dims):
    """(B, S, E) weights over ALL the router's experts, zero off the chosen
    ones, and the chosen ids in ascending order."""
    n_group, topk_group, k, scaling = dims[9:13]
    s = jax.nn.sigmoid(h @ w_router.astype(jnp.float32))
    E = s.shape[-1]
    pick = s + e_bias.astype(jnp.float32)
    groups = pick.reshape(pick.shape[:-1] + (n_group, E // n_group))
    two_best = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
    stay = _largest(two_best, topk_group)
    pick = jnp.where(stay[..., None], groups, -jnp.inf).reshape(pick.shape)
    chosen = _largest(pick, k)
    picked = jnp.where(chosen, s, 0.0)
    weights = scaling * picked / picked.sum(-1, keepdims=True)
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
    ``w_*_e`` hold the experts from ``first_expert`` on, as many as they
    stack). Returns (the held experts' part plus the shared expert, the
    chosen ids (B, S, k) ascending)."""
    dims = dims_of(conf)
    first = int(conf.get("first_expert", 0))
    with jax.default_matmul_precision("highest"):
        weights, ids = _route(h, ep["w_router"], ep["e_bias"], dims)
        y = _swiglu(h, ep["ws_gate"], ep["ws_up"], ep["ws_down"])
        held = ep["w_gate_e"].shape[0]
        for e0 in range(0, held, EXPERT_BLOCK):
            e1 = min(e0 + EXPERT_BLOCK, held)
            y = y + _expert_block(
                h, ep["w_gate_e"][e0:e1], ep["w_up_e"][e0:e1],
                ep["w_down_e"][e0:e1], weights[..., first + e0:first + e1])
    return y, ids


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return _rmsnorm(x, w.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, ln_out, lm_head, eps):
    return _rmsnorm(x, ln_out.astype(jnp.float32), eps) @ lm_head.astype(
        jnp.float32)


def _forward(params: dict, tokens, rows, conf: dict):
    dims = dims_of(conf)
    eps = dims[7]
    period = int(conf["layer_group_size"])
    tokens = np.asarray(tokens, np.int32)
    rows = np.asarray(rows)
    S, R = tokens.shape[1], rows.shape[0]
    tokens = jnp.asarray(np.pad(tokens, ((0, 0), (0, -S % SEQ_BLOCK))))
    rows = np.pad(rows, (0, -R % SEQ_BLOCK))
    n_layers = params["ln_attn"].shape[0]
    n_dense = params["w_gate"].shape[0]
    routed = []
    kda_seen = mla_seen = 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(n_layers):
            h = _norm(x, params["ln_attn"][i], eps)
            if (i + 1) % period == 0:
                x = x + _mla(h, {k: params[k][mla_seen] for k in MLA_LEAVES},
                             dims)
                mla_seen += 1
            else:
                x = x + _kda(h, {k: params[k][kda_seen] for k in KDA_LEAVES},
                             dims)
                kda_seen += 1
            h = _norm(x, params["ln_mlp"][i], eps)
            if i < n_dense:
                x = x + _swiglu(h, *(params[k][i] for k in DENSE_LEAVES))
            else:
                j = i - n_dense
                y, ids = expert_layer(
                    h, {k: params[k][j] for k in ROUTER_LEAVES
                        + EXPERT_LEAVES + SHARED_LEAVES}, conf)
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
    (B, R, V). Every position attends causally to what precedes it."""
    return _forward(params, tokens, rows, conf)[0]


def experts_at(params: dict, tokens, conf: dict) -> np.ndarray:
    """The experts each position chose in each expert layer, ascending:
    (expert layers, B, S, num_experts_per_tok). For the tests that hold the
    program's routing to this one."""
    S = np.asarray(tokens).shape[1]
    return np.stack([np.asarray(r)[:, :S] for r in
                     _forward(params, tokens, np.arange(S), conf)[1]])
