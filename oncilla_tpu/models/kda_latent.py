"""Hybrid linear-attention / latent-attention decoder with group-limited
routed experts (the Ling-3.0-flash shape): the family whose session keeps a
recurrent **carry** beside its pages.

Layer ``i`` attends by latent attention (``mla``, the pieces of
:mod:`~oncilla_tpu.models.latent_moe` with one query matrix and plain
rotary) where ``(i + 1) % layer_group_size == 0``, else by Kimi Delta
Attention (``kda``, arXiv:2510.26692): a gated delta rule with a decay a
channel over a float32 state ``S`` of ``(heads, dk, dv)`` a layer, behind a
short causal convolution. Only the latent layers cache positions, so a
page holds ``n_latent_layers`` layers; a KDA layer's memory is its state
and the convolution's last ``kernel - 1`` inputs, whatever the context.
Feed-forward layers are :mod:`latent_moe`'s: a dense SwiGLU in the leading
layers, then sigmoid-scored experts chosen under a group limit beside one
shared expert; the chip holds ``num_experts`` of the router's
``router_experts`` (``experts_held``) and computes their part. Plain
pre-norm residual, float32.

The KDA layer has two forms over the same carry, each under the ``kda``
scope: :func:`kda_step` advances every row of a batch one token,
:func:`kda_chunk` takes a page's tokens together (intra-chunk terms in
float32 from differences of the cumulated log-decay, which never
overflow). The equations are written out in the plain reference
(``benchmark/references/kda_latent_moe.py``), which shares no code with
this module.

Serving: :data:`PAGED_FAMILY` is what
:class:`~oncilla_tpu.serving.engine.ServingEngine` takes from
``cfg.paged_family``: a page of one latent leaf ``(Lm, 1, 1, P, W)``, a
carry of two leaves a session (``S`` ``(Lk, 1, H, dk, dv)`` and the
convolution inputs ``(Lk, 1, kernel - 1, 3 H dk)``, float32), the fused step
(:func:`kda_decode_batch_step_jit`) and the page program
(:func:`kda_decode_page_jit`), whose context is padded to a power-of-two
number of pages and masked by position.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.models import latent_moe as lm
from oncilla_tpu.models.kv_paging import PagedFamily
from oncilla_tpu.models.llama import rmsnorm

_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class KdaLatentConfig:
    """The published ``config.json`` keys under their own names, plus
    ``dtype`` and the chip's share: ``num_experts`` experts are HELD here,
    ``first_expert`` on, of the ``router_experts`` the router scores."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    num_attention_heads: int = 32
    head_dim: int = 128
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 6144
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    router_experts: int = 512
    first_expert: int = 0
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    layer_group_size: int = 6
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    max_position_embeddings: int = 131072
    rope_theta: float = 6e6
    rope_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.latent_layers:
            raise ValueError(
                f"{self.num_hidden_layers} layers of period "
                f"{self.layer_group_size} hold no latent layer: no page")
        if self.router_experts % self.n_group:
            raise ValueError("n_group does not divide router_experts")

    @classmethod
    def from_published(cls, conf: dict, dtype: str | None = None):
        """From a ``config.json``-shaped dict; keys this family does not
        read are ignored."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in conf.items() if k in names}
        kw["dtype"] = dtype or conf.get("torch_dtype", cls.dtype)
        return cls(**kw)

    def to_published(self) -> dict:
        """The inverse of :meth:`from_published`, ``dtype`` as
        ``torch_dtype``."""
        d = {f.name: getattr(self, f.name) for f in fields(self)}
        d["torch_dtype"] = d.pop("dtype")
        return d

    @staticmethod
    def tiny(**kw) -> "KdaLatentConfig":
        """CI size: four layers of period three (K K M K), one dense; 16
        experts in 4 groups of which 2 stay, 4 a token, all held."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, head_dim=8, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            intermediate_size=96, first_k_dense_replace=1,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
            num_experts=16, router_experts=16, num_experts_per_tok=4,
            n_group=4, topk_group=2, layer_group_size=3,
            max_position_embeddings=4096, dtype="float32")
        base.update(kw)
        return KdaLatentConfig(**base)

    # What the serving engine and latent_moe's shared layers read.
    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def n_routed_experts(self) -> int:
        """The router's width: every expert of the deployment."""
        return self.router_experts

    @property
    def experts_held(self) -> tuple:
        return (self.first_expert, self.num_experts)

    @property
    def latent_layers(self) -> tuple:
        return tuple(i for i in range(self.num_hidden_layers)
                     if (i + 1) % self.layer_group_size == 0)

    @property
    def kda_layers(self) -> tuple:
        return tuple(i for i in range(self.num_hidden_layers)
                     if (i + 1) % self.layer_group_size)

    @property
    def latent_width(self) -> int:
        """Values a position a latent layer holds in the cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY

    def init_params(self, key: jax.Array) -> dict:
        return init_params(key, self)


# -- parameters ----------------------------------------------------------------


def param_spec(cfg: KdaLatentConfig) -> dict:
    """{name: (shape, scale, dtype)} as :func:`latent_moe.param_spec`;
    ``scale`` None is a constant leaf (:data:`_FILL`, else ones). KDA
    leaves are stacked over the KDA layers, latent-attention leaves over
    the latent layers, expert leaves over the HELD experts."""
    c = cfg
    L, D, H, V = c.n_layers, c.hidden_size, c.num_attention_heads, c.vocab
    Lk, Lm = len(c.kda_layers), len(c.latent_layers)
    K, Le = c.first_k_dense_replace, c.n_expert_layers
    F, Fe, Fs = (c.intermediate_size, c.moe_intermediate_size,
                 c.moe_shared_expert_intermediate_size)
    C = H * c.head_dim
    w, f32 = c.dtype, "float32"

    def s_in(fan):
        return 1.0 / math.sqrt(fan)

    def s_out(fan):
        return 1.0 / math.sqrt(2 * L * fan)

    return {
        "embed": ((V, D), 1.0, w),
        "lm_head": ((D, V), s_in(D), w),
        "ln_out": ((D,), None, f32),
        "ln_attn": ((L, D), None, f32),
        "ln_mlp": ((L, D), None, f32),
        # kda: q | k | v in one matrix, the depthwise convolution over
        # them, the decay's projection (full rank), beta | output gate.
        "kda_wqkv": ((Lk, D, 3 * C), s_in(D), w),
        "kda_conv": ((Lk, c.short_conv_kernel_size, 3 * C),
                     s_in(c.short_conv_kernel_size), f32),
        "kda_wf": ((Lk, D, C), s_in(D), w),
        "kda_A_log": ((Lk, H), None, f32),
        "kda_dt_bias": ((Lk, C), None, f32),
        "kda_wbg": ((Lk, D, 2 * H), s_in(D), w),
        "kda_o_norm": ((Lk, c.head_dim), None, f32),
        "kda_wo": ((Lk, C, D), s_out(C), w),
        # mla, q_lora_rank null: one query matrix.
        "wq": ((Lm, D, H * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
               s_in(D), w),
        "wkv_a": ((Lm, D, c.latent_width), s_in(D), w),
        "kv_norm": ((Lm, c.kv_lora_rank), None, f32),
        "wkv_b": ((Lm, c.kv_lora_rank,
                   H * (c.qk_nope_head_dim + c.v_head_dim)),
                  s_in(c.kv_lora_rank), w),
        "wo": ((Lm, H * c.v_head_dim, D), s_out(H * c.v_head_dim), w),
        "w_gate": ((K, D, F), s_in(D), w),
        "w_up": ((K, D, F), s_in(D), w),
        "w_down": ((K, F, D), s_out(F), w),
        "w_router": ((Le, D, c.router_experts), s_in(D), f32),
        "e_bias": ((Le, c.router_experts), None, f32),
        "w_gate_e": ((Le, c.num_experts, D, Fe), s_in(D), w),
        "w_up_e": ((Le, c.num_experts, D, Fe), s_in(D), w),
        "w_down_e": ((Le, c.num_experts, Fe, D), s_out(Fe), w),
        "ws_gate": ((Le, D, Fs), s_in(D), w),
        "ws_up": ((Le, D, Fs), s_in(D), w),
        "ws_down": ((Le, Fs, D), s_out(Fs), w),
    }


# Constant leaves that are not ones. ``kda_dt_bias`` -4 puts the log-decay
# near -0.09 a token (a memory of a dozen tokens, longer in some channels)
# where 0 would forget nine tenths of the state at every token.
_FILL = {"e_bias": 0.0, "kda_A_log": 0.0, "kda_dt_bias": -4.0}


def init_params(key: jax.Array, cfg: KdaLatentConfig) -> dict:
    """Traceable (the benchmark jits it: one call on the device)."""
    spec = param_spec(cfg)
    out = {}
    for k, (name, (shape, scale, dtype)) in zip(
            jax.random.split(key, len(spec)), spec.items()):
        if scale is None:
            out[name] = jnp.full(shape, _FILL.get(name, 1.0),
                                 jnp.dtype(dtype))
        else:
            out[name] = lm._normal(k, shape, scale, jnp.dtype(dtype))
    return out


# -- kda: the gated delta rule ---------------------------------------------------


def _l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _kda_inputs(h, window, params, l: int, cfg: KdaLatentConfig):
    """h: (T, D) float32; window: (T, kernel, 3 H dk), each token's own
    projection last behind the ``kernel - 1`` inputs before it. Returns q
    (normalised, scaled), k (normalised), v, the log-decay g (all (T, H,
    dk)), beta and the output gate (T, H)."""
    dt = jnp.dtype(cfg.dtype)
    H, dk = cfg.num_attention_heads, cfg.head_dim
    T = h.shape[0]
    x = jax.nn.silu((window * params["kda_conv"][l][None]).sum(axis=1))
    q, k, v = (a.reshape(T, H, dk) for a in jnp.split(x, 3, axis=-1))
    q = _l2norm(q) * dk ** -0.5
    k = _l2norm(k)
    f = lm._dot(h, params["kda_wf"][l], "td,dc->tc", dt)
    rate = jnp.exp(params["kda_A_log"][l])[None, :, None]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        rate * (f + params["kda_dt_bias"][l]).reshape(T, H, dk))
    bg = jax.nn.sigmoid(lm._dot(h, params["kda_wbg"][l], "td,dh->th", dt))
    return q, k, v, g, bg[:, :H], bg[:, H:]


def _kda_out(o, gate, params, l: int, cfg: KdaLatentConfig):
    """o: (T, H, dv) -> (T, D): a head's norm, its gate, ``kda_wo``."""
    o = rmsnorm(o, params["kda_o_norm"][l], cfg.rms_norm_eps)
    o = (o * gate[:, :, None]).reshape(o.shape[0], -1)
    return lm._dot(o, params["kda_wo"][l], "ta,ad->td", jnp.dtype(cfg.dtype))


def kda_step(h, state, conv, params, l: int, cfg: KdaLatentConfig):
    """One token a row. h: (B, D) float32; state: (B, H, dk, dv) float32;
    conv: (B, kernel - 1, 3 H dk). ``S' = Diag(a) S``, ``S = S' + k (b (v -
    S'^T k))^T``, ``o = S^T q``: products with the state are elementwise
    and summed (a float32 einsum would go through the MXU in bf16
    passes). Returns (y (B, D), new state, new conv)."""
    dt = jnp.dtype(cfg.dtype)
    x = lm._dot(h, params["kda_wqkv"][l], "td,dc->tc", dt)
    window = jnp.concatenate([conv, x[:, None, :]], axis=1)
    q, k, v, g, beta, gate = _kda_inputs(h, window, params, l, cfg)
    decayed = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - (decayed * k[..., None]).sum(axis=-2))
    state = decayed + k[..., None] * u[..., None, :]
    o = (state * q[..., None]).sum(axis=-2)
    return _kda_out(o, gate, params, l, cfg), state, window[:, 1:]


def kda_chunk(h, state, conv, params, l: int, cfg: KdaLatentConfig):
    """A chunk of T tokens of ONE sequence together. h: (T, D); state: (H,
    dk, dv); conv: (kernel - 1, 3 H dk). With G the log-decay cumulated
    over the chunk, every intra-chunk term carries ``exp(G_t - G_s)`` for
    ``s <= t``, at most 1; the pseudo-values ``u`` solve a unit lower
    triangular system by forward substitution. Returns (y (T, D), the
    state after the chunk, the chunk's last ``kernel - 1`` inputs)."""
    dt = jnp.dtype(cfg.dtype)
    T = h.shape[0]
    Kc = cfg.short_conv_kernel_size
    x = lm._dot(h, params["kda_wqkv"][l], "td,dc->tc", dt)
    seq = jnp.concatenate([conv, x], axis=0)
    window = jnp.stack([seq[j:j + T] for j in range(Kc)], axis=1)
    q, k, v, g, beta, gate = (
        a.swapaxes(0, 1)
        for a in _kda_inputs(h, window, params, l, cfg))    # (H, T, .)
    G = jnp.cumsum(g, axis=1)
    lower = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.exp(jnp.where(lower[None, :, :, None],
                              G[:, :, None, :] - G[:, None, :, :], -jnp.inf))
    kk = (k[:, :, None, :] * k[:, None, :, :] * decay).sum(-1)   # (H, T, T)
    qk = (q[:, :, None, :] * k[:, None, :, :] * decay).sum(-1)
    kk = jnp.where(jnp.tril(lower, -1)[None], kk, 0.0)
    eG = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum(
        "htc,hcv->htv", k * eG, state, precision=_HI))

    def row(t, u):
        pred = (jax.lax.dynamic_index_in_dim(kk, t, 1, False)[:, :, None]
                * u).sum(axis=1)
        new = (jax.lax.dynamic_index_in_dim(rhs, t, 1, False)
               - jax.lax.dynamic_index_in_dim(beta, t, 1, False)[:, None]
               * pred)
        return jax.lax.dynamic_update_index_in_dim(u, new, t, 1)

    u = jax.lax.fori_loop(0, T, row, jnp.zeros_like(rhs))
    o = (jnp.einsum("htc,hcv->htv", q * eG, state, precision=_HI)
         + jnp.einsum("hts,hsv->htv", qk, u, precision=_HI))
    to_end = jnp.exp(G[:, -1:, :] - G)
    state = (eG[:, -1, :, None] * state
             + jnp.einsum("htc,htv->hcv", k * to_end, u, precision=_HI))
    y = _kda_out(o.swapaxes(0, 1), gate.swapaxes(0, 1), params, l, cfg)
    return y, state, seq[T:]


# -- the layers ----------------------------------------------------------------------


def _block(x, params, i: int, real, cfg: KdaLatentConfig, attend):
    """Pre-norm residual layer ``i``: ``attend(h)`` then the FFN. Returns
    (x, distinct held experts touched, chosen experts | None)."""
    eps = cfg.rms_norm_eps
    x = x + attend(rmsnorm(x, params["ln_attn"][i], eps))
    y, (n_hit, idx) = lm._ffn(rmsnorm(x, params["ln_mlp"][i], eps), params,
                              i, real, cfg)
    return x + y, n_hit, idx


def _logits(params, x, cfg: KdaLatentConfig):
    x = rmsnorm(x, params["ln_out"], cfg.rms_norm_eps)
    return lm._dot(x, params["lm_head"], "td,dv->tv", jnp.dtype(cfg.dtype))


# -- the paged programs --------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(6, 7, 8))
def kda_decode_batch_step_jit(
    params: dict,
    tokens: jax.Array,     # (B,) current token ids, one per session
    meta: jax.Array,       # (B, 4) int32 [pos, tail_len, ctx_len, -]
    n_real: jax.Array,     # () int32: rows [0, n_real) are sessions
    pool: jax.Array,       # (N, Lm, 1, P, W) resident page pool
    table: jax.Array,      # (B, MP) int32 pool row per context page
    tail: jax.Array,       # (Lm, B, 1, P, W) per-session tails (donated)
    state: jax.Array,      # (Lk, B, H, dk, dv) float32 (donated)
    conv: jax.Array,       # (Lk, B, kernel - 1, 3 H dk) float32 (donated)
    cfg: KdaLatentConfig,
):
    """ONE fused decode step for a batch of sessions: the latent layers
    as ``latent_moe.latent_decode_batch_step_jit`` (same block table, same
    masking, same tail insertion), the KDA layers each row from its own
    carry. Rows at and past ``n_real`` are padding: routed to no expert,
    counted nowhere, their carry left as it was. Returns (logits (B, V)
    float32, new tail, new state, new conv, () int32 distinct (layer,
    held expert) pairs that received a real token)."""
    pos, tail_len, ctx_len = meta[:, 0], meta[:, 1], meta[:, 2]
    Lm, B, _, P, W = tail.shape
    C = table.shape[1] * P
    dt = jnp.dtype(cfg.dtype)
    real = jnp.arange(B) < n_real
    ctx = jnp.take(pool, table, axis=0)[:, :, :, 0].transpose(
        2, 0, 1, 3, 4).reshape(Lm, B, C, W)
    valid = jnp.concatenate(
        [jnp.arange(C)[None, :] < ctx_len[:, None],
         jnp.arange(P)[None, :] <= tail_len[:, None]], axis=1)
    slot = (jnp.arange(P)[None, :] == tail_len[:, None])[:, :, None]
    live = (tail_len > 0)[:, None, None]
    x = params["embed"][tokens].astype(jnp.float32)
    touched = jnp.int32(0)
    for i in range(cfg.n_layers):
        box = {}
        if i in cfg.latent_layers:
            def attend(h, m=cfg.latent_layers.index(i), box=box):
                with jax.named_scope("mla"):
                    qn, qr, entry = lm.latent_qkv(h, params, m, pos, cfg)
                    t = jnp.where(slot, entry[:, None, :].astype(tail.dtype),
                                  jnp.where(live, tail[m, :, 0], 0))
                    box["tail"] = (m, t)
                    latent = jnp.concatenate(
                        [ctx[m].astype(dt), t.astype(dt)], axis=1)
                    return lm.attend_absorbed(qn, qr, latent, valid, params,
                                              m, cfg)
        else:
            def attend(h, l=cfg.kda_layers.index(i), box=box):
                with jax.named_scope("kda"):
                    y, s, c = kda_step(h, state[l], conv[l], params, l, cfg)
                    box["carry"] = (
                        l, jnp.where(real[:, None, None, None], s, state[l]),
                        jnp.where(real[:, None, None], c, conv[l]))
                    return y
        x, n_hit, _ = _block(x, params, i, real, cfg, attend)
        if "tail" in box:
            m, t = box["tail"]
            tail = tail.at[m, :, 0].set(t)
        else:
            l, s, c = box["carry"]
            state, conv = state.at[l].set(s), conv.at[l].set(c)
        touched = touched + n_hit
    return _logits(params, x, cfg), tail, state, conv, touched


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4, 5, 6))
def kda_decode_page_jit(
    params: dict,
    tokens_page: jax.Array,  # (1, P) one full page of token ids
    meta: jax.Array,         # (2,) int32 [pos0, -]
    ctx: jax.Array,          # (Lm, 1, 1, C, W) context; positions >= pos0 pad
    tail: jax.Array,         # (Lm, 1, 1, P, W) tail buffer (donated)
    state: jax.Array,        # (Lk, 1, H, dk, dv) float32 (donated)
    conv: jax.Array,         # (Lk, 1, kernel - 1, 3 H dk) float32 (donated)
    cfg: KdaLatentConfig,
):
    """One full page of prefill as ONE program that takes the page's P
    tokens through each layer together: expanded latent attention over the
    context's first ``pos0`` positions (the rest of ``ctx`` is padding, so
    one program serves every context up to its length) and, causally, the
    page's own entries; the KDA layers chunk-wise from the carry. Returns
    (logits (1, P, V), the full tail, new state, new conv, () int32
    distinct (layer, held expert) pairs touched)."""
    P = tail.shape[3]
    C = ctx.shape[3]
    dt = jnp.dtype(cfg.dtype)
    positions = meta[0] + jnp.arange(P)
    mask = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(C)[None, :] < meta[0], (P, C)),
         jnp.tril(jnp.ones((P, P), bool))], axis=1)
    real = jnp.ones((P,), bool)
    x = params["embed"][tokens_page[0]].astype(jnp.float32)
    touched = jnp.int32(0)
    for i in range(cfg.n_layers):
        box = {}
        if i in cfg.latent_layers:
            def attend(h, m=cfg.latent_layers.index(i), box=box):
                with jax.named_scope("mla"):
                    qn, qr, entry = lm.latent_qkv(h, params, m, positions,
                                                  cfg)
                    box["tail"] = (m, entry.astype(tail.dtype))
                    latent = jnp.concatenate(
                        [ctx[m, 0, 0].astype(dt), entry.astype(dt)], axis=0)
                    return lm.attend_expanded(qn, qr, latent, mask, params,
                                              m, cfg)
        else:
            def attend(h, l=cfg.kda_layers.index(i), box=box):
                with jax.named_scope("kda"):
                    y, s, c = kda_chunk(h, state[l, 0], conv[l, 0], params,
                                        l, cfg)
                    box["carry"] = (l, s, c)
                    return y
        x, n_hit, _ = _block(x, params, i, real, cfg, attend)
        if "tail" in box:
            m, t = box["tail"]
            tail = tail.at[m, 0, 0].set(t)
        else:
            l, s, c = box["carry"]
            state, conv = state.at[l, 0].set(s), conv.at[l, 0].set(c)
        touched = touched + n_hit
    return _logits(params, x, cfg)[None], tail, state, conv, touched


def _leaf_dims(cfg: KdaLatentConfig) -> tuple:
    return (1, cfg.latent_width)


def _cached_layers(cfg: KdaLatentConfig) -> int:
    return len(cfg.latent_layers)


def _carry_leaves(cfg: KdaLatentConfig, batch: int = 1) -> tuple:
    Lk, H, dk = len(cfg.kda_layers), cfg.num_attention_heads, cfg.head_dim
    return (((Lk, batch, H, dk, dk), jnp.float32),
            ((Lk, batch, cfg.short_conv_kernel_size - 1, 3 * H * dk),
             jnp.float32))


def _step(params, tokens, meta, n_real, pool, table, tails, cfg, carry):
    logits, tail, state, conv, touched = kda_decode_batch_step_jit(
        params, tokens, meta, np.int32(n_real), pool[0], table, tails[0],
        *carry, cfg)
    return logits, (tail,), touched, (state, conv)


def _page(params, tokens_page, meta, ctx, tails, cfg, carry):
    # The context's pages snap up to a power of two: log(n) page programs
    # serve every prompt length.
    leaf = ctx[0]
    P = tails[0].shape[3]
    pages = leaf.shape[3] // P
    pad = ((1 << (pages - 1).bit_length()) - pages) * P if pages else 0
    if pad:
        leaf = jnp.pad(leaf, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    logits, tail, state, conv, touched = kda_decode_page_jit(
        params, tokens_page, meta, leaf, tails[0], *carry, cfg)
    return logits, (tail,), touched, (state, conv)


def _assignments_per_token(cfg: KdaLatentConfig) -> int:
    return cfg.num_experts_per_tok * cfg.n_expert_layers


PAGED_FAMILY = PagedFamily(
    n_leaves=1, leaf_dims=_leaf_dims, step=_step, page=_page,
    write_row=lm._write_row, assignments_per_token=_assignments_per_token,
    cached_layers=_cached_layers, carry_leaves=_carry_leaves,
)
