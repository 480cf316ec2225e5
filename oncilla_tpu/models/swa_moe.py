"""Window- and full-attention decoder with routed experts of which the chip
holds a share: the family whose page comes in two **kinds**. Two published
shapes run through it: Laguna (per-head gates, a head count a kind, a
leading dense layer, sigmoid-scored experts beside a shared one) and
Mellum2 (no gate, one head count, every layer sparse, softmax-scored
experts and no shared one).

Layer ``i`` is a grouped-query attention layer of ``heads_per_layer[i]``
query heads over ``num_key_value_heads`` KV heads. A ``full_attention``
layer attends causally to every position and rotates the leading
``full_partial_rotary_factor`` of a head by YaRN frequencies, cos and sin
scaled by ``full_attention_factor``; a ``sliding_attention`` layer attends
to its last ``sliding_window`` positions and rotates by plain frequencies.
With ``gating`` ``per-head`` every head's output is gated by a sigmoid of
the layer's normed input before the output projection; with ``none`` it
is not. Feed-forward layers are :mod:`~oncilla_tpu.models.latent_moe`'s as
they stand: a dense SwiGLU in the leading layers (none where every
``mlp_layer_types`` entry is sparse), then experts scored by
``scoring_func`` beside one shared expert (none where its width is 0); the
chip holds ``num_experts`` of the router's ``router_experts``
(``experts_held``) and computes their part. Plain pre-norm residual,
float32. Mechanisms sit under the scopes ``attn_full``, ``attn_window``,
``gate`` (where there is one), ``router`` and ``experts``. The equations
are written out in the plain references (``benchmark/references/
swa_gqa_moe.py``, ``swa_gqa_softmax_moe.py``), which share no code with
this module.

Serving: :data:`PAGED_FAMILY` is what
:class:`~oncilla_tpu.serving.engine.ServingEngine` takes from
``cfg.paged_family``. A full layer needs every position of a session and a
window layer the last ``sliding_window`` only, so the page has two kinds
(:class:`~oncilla_tpu.models.kv_paging.PageKind`), each a K and a V over its
own layers: the full kind ``(Lf, 1, KV, P, Hd)``, kept while the session
lives, and the window kind ``(Lw, 1, KV, P, Hd)``, which the engine drops
once it has left the window. The fused step
(:func:`swa_decode_batch_step_jit`) reads a page pool and a block table a
kind; the page program (:func:`swa_decode_page_jit`) a full-kind context
padded to a power-of-two number of pages and a window-kind context of one
size, both masked by position, and up to ``PAGED_FAMILY.chunk_pages`` pages
of one prompt at once.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.models import latent_moe as lm
from oncilla_tpu.models.kv_paging import (
    PagedFamily,
    PageKind,
    paged_pool_write_row_jit,
)
from oncilla_tpu.models.llama import rmsnorm

FULL, WINDOW = "full_attention", "sliding_attention"


@dataclass(frozen=True)
class SwaMoeConfig:
    """The published ``config.json`` keys under their own names (the nested
    ``rope_parameters`` flattened to ``full_*`` and ``window_*``, the lists
    as tuples of ``num_hidden_layers`` entries), plus ``dtype`` and the
    chip's share: ``num_experts`` experts are HELD here, ``first_expert``
    on, of the ``router_experts`` the router scores. The defaults are
    Laguna's; ``gating`` ``none``, a ``shared_expert_intermediate_size``
    of 0, every ``mlp_layer_types`` entry sparse and ``scoring_func``
    ``softmax`` take the gate, the shared expert, the dense layers and the
    sigmoid router out (leaves, scopes and all)."""

    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: tuple = (FULL, WINDOW, WINDOW, WINDOW) * 12
    num_attention_heads_per_layer: tuple = (48, 72, 72, 72) * 12
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 47
    sliding_window: int = 512
    num_experts: int = 256
    router_experts: int = 256
    first_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    moe_routed_scaling_factor: float = 2.5
    full_rope_theta: float = 500000.0
    full_factor: float = 128.0
    full_original_max_position_embeddings: int = 8192
    full_beta_fast: float = 32.0
    full_beta_slow: float = 1.0
    full_attention_factor: float = 1.4852030263919618
    full_partial_rotary_factor: float = 0.5
    window_rope_theta: float = 10000.0
    window_partial_rotary_factor: float = 1.0
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    gating: str = "per-head"
    scoring_func: str = "sigmoid"
    dtype: str = "bfloat16"

    def __post_init__(self):
        L = self.num_hidden_layers
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            if len(getattr(self, name)) != L:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {L} layers")
        if not self.full_layers or not self.window_layers:
            raise ValueError("a page kind with no layer: the family needs a "
                             "full and a window layer at least")
        if set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if self.gating not in ("per-head", "none"):
            raise ValueError(f"gating {self.gating!r}")
        if self.scoring_func not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring_func {self.scoring_func!r}")
        K = self.first_k_dense_replace
        if "dense" in self.mlp_layer_types[K:]:
            raise ValueError("dense layers after the first expert layer")
        for layers in (self.full_layers, self.window_layers):
            heads = {self.num_attention_heads_per_layer[i] for i in layers}
            if len(heads) != 1 or heads.pop() % self.num_key_value_heads:
                raise ValueError(
                    "the layers of a kind share one head count, a multiple "
                    "of num_key_value_heads")

    @classmethod
    def from_published(cls, conf: dict, dtype: str | None = None):
        """From a ``config.json``-shaped dict; keys this family does not
        read are ignored, and the lists may run past
        ``num_hidden_layers`` (a cut in depth reads their head)."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in conf.items() if k in names}
        L = kw.get("num_hidden_layers", cls.num_hidden_layers)
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            if name in kw:
                kw[name] = tuple(kw[name][:L])
        rope = conf.get("rope_parameters") or {}
        for kind, pre in ((FULL, "full_"), (WINDOW, "window_")):
            for k, v in (rope.get(kind) or {}).items():
                if pre + k in names:
                    kw[pre + k] = v
        kw["dtype"] = dtype or conf.get("torch_dtype", cls.dtype)
        return cls(**kw)

    def to_published(self) -> dict:
        """The inverse of :meth:`from_published`: ``rope_parameters`` a
        group again, the lists as lists, ``dtype`` as ``torch_dtype``."""
        d = {f.name: getattr(self, f.name) for f in fields(self)}
        rope = {FULL: {"rope_type": "yarn"}, WINDOW: {"rope_type": "default"}}
        for kind, pre in ((FULL, "full_"), (WINDOW, "window_")):
            for k in [k for k in d if k.startswith(pre)]:
                rope[kind][k[len(pre):]] = d.pop(k)
        d["rope_parameters"] = rope
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            d[name] = list(d[name])
        d["torch_dtype"] = d.pop("dtype")
        return d

    @staticmethod
    def tiny(**kw) -> "SwaMoeConfig":
        """CI size: five layers F W W W F as the published cut, a window
        of ten positions, 16 experts of which 4 a token, all held; rotary
        numbers at which YaRN's ramp has values between 0 and 1."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
            layer_types=(FULL, WINDOW, WINDOW, WINDOW, FULL),
            num_attention_heads_per_layer=(4, 6, 6, 6, 4),
            mlp_layer_types=("dense",) + ("sparse",) * 4, sliding_window=10,
            num_experts=16, router_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            full_rope_theta=100.0, full_factor=4.0,
            full_original_max_position_embeddings=64,
            full_attention_factor=1.2, max_position_embeddings=4096,
            dtype="float32")
        base.update(kw)
        return SwaMoeConfig(**base)

    @staticmethod
    def tiny_softmax(**kw) -> "SwaMoeConfig":
        """CI size of the Mellum2 shape: one period W W W F, one head count
        (8 over 2 KV heads), no gate, every layer sparse, 16 experts all
        held of which 4 a token by softmax, no shared expert, the whole head
        rotated by YaRN (numbers at which its ramp has values between 0 and
        1), a window of ten positions."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            num_hidden_layers=4, num_key_value_heads=2, head_dim=16,
            layer_types=(WINDOW, WINDOW, WINDOW, FULL),
            num_attention_heads_per_layer=(8,) * 4,
            mlp_layer_types=("sparse",) * 4, sliding_window=10,
            num_experts=16, router_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=32, shared_expert_intermediate_size=0,
            moe_routed_scaling_factor=1.0, full_rope_theta=100.0,
            full_factor=4.0, full_original_max_position_embeddings=64,
            full_attention_factor=1.2, full_partial_rotary_factor=1.0,
            window_rope_theta=100.0, max_position_embeddings=4096,
            gating="none", scoring_func="softmax", dtype="float32")
        base.update(kw)
        return SwaMoeConfig(**base)

    # What the serving engine and latent_moe's shared layers read.
    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def first_k_dense_replace(self) -> int:
        return self.mlp_layer_types.index("sparse")

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
    def n_group(self) -> int:
        return 1

    @property
    def topk_group(self) -> int:
        return 1

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == WINDOW)

    def window_pages(self, page_tokens: int) -> int:
        """The most window-kind pages a session holds once those that left
        the window are dropped: the page program's window context."""
        return -(-self.sliding_window // page_tokens)

    @property
    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY

    def init_params(self, key: jax.Array) -> dict:
        return init_params(key, self)


# -- parameters ----------------------------------------------------------------


def _kind_of(cfg: SwaMoeConfig, i: int) -> tuple:
    """Layer ``i``'s leaves: their prefix, its index among its kind, whether
    it attends to every position."""
    if cfg.layer_types[i] == FULL:
        return "f", cfg.full_layers.index(i), True
    return "w", cfg.window_layers.index(i), False


def _kv_at(full: bool) -> int:
    """Where a kind's K lies (its V follows) among a page's leaves: full K,
    full V, window K, window V."""
    return 0 if full else 2


def param_spec(cfg: SwaMoeConfig) -> dict:
    """{name: (shape, scale, dtype)} as :func:`latent_moe.param_spec`;
    ``scale`` None is a constant leaf (ones; the selection bias zeros).
    Attention leaves are stacked over the layers of their kind (``f_``
    full, ``w_`` window: their head counts differ), expert leaves over the
    HELD experts."""
    c = cfg
    L, D, V = c.n_layers, c.hidden_size, c.vocab
    K, Le = c.first_k_dense_replace, c.n_expert_layers
    F, Fe, Fs = (c.intermediate_size, c.moe_intermediate_size,
                 c.shared_expert_intermediate_size)
    KVd = c.num_key_value_heads * c.head_dim
    w, f32 = c.dtype, "float32"

    def s_in(fan):
        return 1.0 / math.sqrt(fan)

    def s_out(fan):
        return 1.0 / math.sqrt(2 * L * fan)

    spec = {
        "embed": ((V, D), 1.0, w),
        "lm_head": ((D, V), s_in(D), w),
        "ln_out": ((D,), None, f32),
        "ln_attn": ((L, D), None, f32),
        "ln_mlp": ((L, D), None, f32),
    }
    for pre, layers in (("f", c.full_layers), ("w", c.window_layers)):
        n = len(layers)
        H = c.num_attention_heads_per_layer[layers[0]]
        spec.update({
            f"{pre}_wq": ((n, D, H * c.head_dim), s_in(D), w),
            f"{pre}_wk": ((n, D, KVd), s_in(D), w),
            f"{pre}_wv": ((n, D, KVd), s_in(D), w),
        })
        if c.gating == "per-head":
            spec[f"{pre}_wg"] = ((n, D, H), s_in(D), w)
        spec[f"{pre}_wo"] = ((n, H * c.head_dim, D), s_out(H * c.head_dim), w)
    if K:
        spec.update({
            "w_gate": ((K, D, F), s_in(D), w),
            "w_up": ((K, D, F), s_in(D), w),
            "w_down": ((K, F, D), s_out(F), w),
        })
    spec["w_router"] = ((Le, D, c.router_experts), s_in(D), f32)
    if c.scoring_func == "sigmoid":
        spec["e_bias"] = ((Le, c.router_experts), None, f32)
    spec.update({
        "w_gate_e": ((Le, c.num_experts, D, Fe), s_in(D), w),
        "w_up_e": ((Le, c.num_experts, D, Fe), s_in(D), w),
        "w_down_e": ((Le, c.num_experts, Fe, D), s_out(Fe), w),
    })
    if Fs:
        spec.update({
            "ws_gate": ((Le, D, Fs), s_in(D), w),
            "ws_up": ((Le, D, Fs), s_in(D), w),
            "ws_down": ((Le, Fs, D), s_out(Fs), w),
        })
    return spec


def init_params(key: jax.Array, cfg: SwaMoeConfig) -> dict:
    """Traceable (the benchmark jits it: one call on the device)."""
    spec = param_spec(cfg)
    out = {}
    for k, (name, (shape, scale, dtype)) in zip(
            jax.random.split(key, len(spec)), spec.items()):
        if scale is None:
            out[name] = jnp.full(shape, 0.0 if name == "e_bias" else 1.0,
                                 jnp.dtype(dtype))
        else:
            out[name] = lm._normal(k, shape, scale, jnp.dtype(dtype))
    return out


# -- attention -------------------------------------------------------------------


def rope_of(cfg: SwaMoeConfig, full: bool) -> tuple:
    """(inverse frequencies, the factor on cos and sin) of a layer kind.
    The frequencies number half the rotated width: a full layer rotates the
    leading ``full_partial_rotary_factor`` of a head by YaRN's blend of
    plain and interpolated frequencies, a window layer by plain ones."""
    if not full:
        dr = int(cfg.head_dim * cfg.window_partial_rotary_factor)
        exponent = np.arange(0, dr, 2, dtype=np.float64) / dr
        return (1.0 / cfg.window_rope_theta ** exponent).astype(
            np.float32), 1.0
    dr = int(cfg.head_dim * cfg.full_partial_rotary_factor)
    theta = cfg.full_rope_theta
    exponent = np.arange(0, dr, 2, dtype=np.float64) / dr
    extra = 1.0 / theta ** exponent
    inter = extra / cfg.full_factor

    def correction_dim(rotations):
        return (dr * math.log(cfg.full_original_max_position_embeddings
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(cfg.full_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.full_beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return ((inter * ramp + extra * (1.0 - ramp)).astype(np.float32),
            cfg.full_attention_factor)


def _rotate(x, positions, inv_freq, factor: float):
    """x: (T, heads, Hd) float32; positions: (T,). Adjacent pairs of the
    leading ``2 * len(inv_freq)`` values; the rest pass."""
    dr = 2 * inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq[None, None]
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    x1, x2 = x[..., 0:dr:2], x[..., 1:dr:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       axis=-1).reshape(x.shape[:-1] + (dr,))
    return jnp.concatenate([turned, x[..., dr:]], axis=-1)


def qkv_gate(h, params, i: int, positions, cfg: SwaMoeConfig):
    """h: (T, D) float32 -> rotated q (T, H, Hd), rotated k and v (T, KV,
    Hd) and the heads' gates (T, H), all float32; the gates are None where
    ``cfg.gating`` is ``none``."""
    dt = jnp.dtype(cfg.dtype)
    pre, m, full = _kind_of(cfg, i)
    T, hd = h.shape[0], cfg.head_dim
    inv_freq, factor = rope_of(cfg, full)
    q = lm._dot(h, params[f"{pre}_wq"][m], "td,da->ta", dt).reshape(T, -1, hd)
    k = lm._dot(h, params[f"{pre}_wk"][m], "td,da->ta", dt).reshape(T, -1, hd)
    v = lm._dot(h, params[f"{pre}_wv"][m], "td,da->ta", dt).reshape(T, -1, hd)
    gate = None
    if cfg.gating == "per-head":
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(
                lm._dot(h, params[f"{pre}_wg"][m], "td,dh->th", dt))
    return (_rotate(q, positions, inv_freq, factor),
            _rotate(k, positions, inv_freq, factor), v, gate)


def gated_out(o, gate, params, i: int, cfg: SwaMoeConfig):
    """o: (T, H, Hd), gate: (T, H) or None -> (T, D): each head by its
    gate, where there is one, then the output projection."""
    pre, m, _ = _kind_of(cfg, i)
    if gate is not None:
        with jax.named_scope("gate"):
            o = o * gate[:, :, None]
    return lm._dot(o.reshape(o.shape[0], -1), params[f"{pre}_wo"][m],
                   "ta,ad->td", jnp.dtype(cfg.dtype))


def attend_seq(q, k, v, mask, cfg: SwaMoeConfig):
    """One sequence's queries over one set of keys. q: (S, H, Hd); k, v:
    (KV, C, Hd); mask: (S, C). Query head ``h`` reads KV head ``h // (H /
    KV)``."""
    dt = jnp.dtype(cfg.dtype)
    S, H, hd = q.shape
    KV = k.shape[0]
    qg = q.reshape(S, KV, H // KV, hd)
    s = lm._dot(qg, k, "skgd,kcd->kgsc", dt) * hd ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
    return lm._dot(p, v, "kgsc,kcd->skgd", dt).reshape(S, H, hd)


def attend_paged(q, ctx_k, ctx_v, tail_k, tail_v, mask_ctx, mask_tail,
                 cfg: SwaMoeConfig):
    """A batch's queries, each over its own context: pages as the block
    table gathered them and the tail. q: (B, H, Hd); ctx_*: (B, MP, KV, P,
    Hd); tail_*: (B, KV, P, Hd); mask_ctx: (B, MP * P); mask_tail: (B, P).
    One softmax over both; the pages are read as they lie."""
    dt = jnp.dtype(cfg.dtype)
    B, H, hd = q.shape
    _, MP, KV, P, _ = ctx_k.shape
    qg = q.reshape(B, KV, H // KV, hd)
    s = jnp.concatenate(
        [lm._dot(qg, ctx_k, "bkgd,bmkpd->bkgmp", dt).reshape(
            B, KV, H // KV, MP * P),
         lm._dot(qg, tail_k, "bkgd,bkpd->bkgp", dt)], axis=-1) * hd ** -0.5
    mask = jnp.concatenate([mask_ctx, mask_tail], axis=1)
    p = jax.nn.softmax(jnp.where(mask[:, None, None, :], s, -1e30), axis=-1)
    o = (lm._dot(p[..., :MP * P].reshape(B, KV, H // KV, MP, P), ctx_v,
                 "bkgmp,bmkpd->bkgd", dt)
         + lm._dot(p[..., MP * P:], tail_v, "bkgp,bkpd->bkgd", dt))
    return o.reshape(B, H, hd)


# -- the layers ----------------------------------------------------------------------


def _scope(cfg: SwaMoeConfig, i: int):
    return jax.named_scope(
        "attn_full" if cfg.layer_types[i] == FULL else "attn_window")


def _block(x, params, i: int, real, cfg: SwaMoeConfig, attend):
    """Pre-norm residual layer ``i``: ``attend(h)`` then the FFN. Returns
    (x, distinct held experts touched, chosen experts | None)."""
    eps = cfg.rms_norm_eps
    with _scope(cfg, i):
        x = x + attend(rmsnorm(x, params["ln_attn"][i], eps))
    y, (n_hit, idx) = lm._ffn(rmsnorm(x, params["ln_mlp"][i], eps), params,
                              i, real, cfg)
    return x + y, n_hit, idx


def _logits(params, x, cfg: SwaMoeConfig):
    x = rmsnorm(x, params["ln_out"], cfg.rms_norm_eps)
    return lm._dot(x, params["lm_head"], "td,dv->tv", jnp.dtype(cfg.dtype))


def _in_reach(cfg: SwaMoeConfig, full: bool, keys, queries):
    """Which keys a layer's queries may see besides causality: all for a
    full layer, the last ``sliding_window`` for a window layer. keys: (...,
    C) positions; queries: (..., 1) positions."""
    if full:
        return jnp.ones(jnp.broadcast_shapes(keys.shape, queries.shape), bool)
    return keys > queries - cfg.sliding_window


def forward(params: dict, tokens: jax.Array, cfg: SwaMoeConfig,
            return_routing: bool = False):
    """Logits (B, S, V) float32 of a token batch, every position attending
    causally (a window layer: to its last ``sliding_window`` positions): no
    cache. With ``return_routing`` also the experts chosen, (expert layers,
    B, S, k)."""
    B, S = tokens.shape
    positions = jnp.tile(jnp.arange(S), B)
    at = jnp.arange(S)
    causal = at[None, :] <= at[:, None]
    real = jnp.ones((B * S,), bool)
    x = params["embed"][tokens.reshape(-1)].astype(jnp.float32)
    routing = []
    for i in range(cfg.n_layers):
        def attend(h, i=i):
            q, k, v, gate = qkv_gate(h, params, i, positions, cfg)
            mask = causal & _in_reach(cfg, _kind_of(cfg, i)[2], at[None, :],
                                      at[:, None])
            q, k, v = (a.reshape((B, S) + a.shape[1:]) for a in (q, k, v))
            o = jax.vmap(lambda a, b, c: attend_seq(
                a, b.swapaxes(0, 1), c.swapaxes(0, 1), mask, cfg))(q, k, v)
            return gated_out(o.reshape((B * S,) + o.shape[2:]), gate, params,
                             i, cfg)

        x, _, idx = _block(x, params, i, real, cfg, attend)
        if idx is not None:
            routing.append(idx.reshape(B, S, -1))
    logits = _logits(params, x, cfg).reshape(B, S, -1)
    return (logits, jnp.stack(routing)) if return_routing else logits


# -- the paged programs --------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("tails",))
def swa_decode_batch_step_jit(
    params: dict,
    tokens: jax.Array,     # (B,) current token ids, one per session
    meta: jax.Array,       # (B, 6) int32 [pos, tail_len, then a kind
                           #  (full, window): ctx_len, ctx_start]
    n_real: jax.Array,     # () int32: rows [0, n_real) are sessions
    pool: tuple,           # (N, L, KV, P, Hd): full K, V, window K, V
    tables: tuple,         # (B, MP) int32 pool rows: full, window
    tails: tuple,          # (L, B, KV, P, Hd) as pool (donated)
    cfg: SwaMoeConfig,
):
    """ONE fused decode step for a batch of sessions whose pages are of two
    kinds: each kind has its page pool, its block table (padded slots
    gather row 0 and are masked by ``ctx_len``) and the position its first
    page starts at (``ctx_start``: the window kind's earlier pages are
    gone). Tail insertion and the reading of a row with ``tail_len`` 0 as
    zeros are ``kv_paging.paged_decode_batch_step_jit``'s. Rows at and past
    ``n_real`` are padding: routed to no expert, counted nowhere. Returns
    (logits (B, V) float32, new tails, () int32 distinct (layer, held
    expert) pairs that received a real token)."""
    pos, tail_len = meta[:, 0], meta[:, 1]
    B = tokens.shape[0]
    P = tails[0].shape[3]
    real = jnp.arange(B) < n_real
    at_tail = (pos - tail_len)[:, None] + jnp.arange(P)[None, :]
    in_tail = jnp.arange(P)[None, :] <= tail_len[:, None]
    slot = (jnp.arange(P)[None, :] == tail_len[:, None])[:, None, :, None]
    live = (tail_len > 0)[:, None, None, None]
    masks = {}
    for full, n in ((True, 0), (False, 1)):
        C = tables[n].shape[1] * P
        ctx_len, ctx_start = meta[:, 2 + 2 * n], meta[:, 3 + 2 * n]
        at_ctx = ctx_start[:, None] + jnp.arange(C)[None, :]
        masks[full] = (
            (jnp.arange(C)[None, :] < ctx_len[:, None])
            & _in_reach(cfg, full, at_ctx, pos[:, None]),
            in_tail & _in_reach(cfg, full, at_tail, pos[:, None]))
    x = params["embed"][tokens].astype(jnp.float32)
    new_tails = list(tails)
    touched = jnp.int32(0)
    for i in range(cfg.n_layers):
        _, m, full = _kind_of(cfg, i)
        box = {}

        at = _kv_at(full)

        def attend(h, i=i, m=m, full=full, at=at, box=box):
            q, k, v, gate = qkv_gate(h, params, i, pos, cfg)
            tail_k, tail_v = tails[at], tails[at + 1]
            tk = jnp.where(slot, k[:, :, None, :].astype(tail_k.dtype),
                           jnp.where(live, tail_k[m], 0))
            tv = jnp.where(slot, v[:, :, None, :].astype(tail_v.dtype),
                           jnp.where(live, tail_v[m], 0))
            box["tails"] = (tk, tv)
            table = tables[at // 2]
            o = attend_paged(
                q, jnp.take(pool[at][:, m], table, axis=0),
                jnp.take(pool[at + 1][:, m], table, axis=0), tk, tv,
                *masks[full], cfg)
            return gated_out(o, gate, params, i, cfg)

        x, n_hit, _ = _block(x, params, i, real, cfg, attend)
        new_tails[at] = new_tails[at].at[m].set(box["tails"][0])
        new_tails[at + 1] = new_tails[at + 1].at[m].set(box["tails"][1])
        touched = touched + n_hit
    return _logits(params, x, cfg), tuple(new_tails), touched


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("tails",))
def swa_decode_page_jit(
    params: dict,
    tokens_page: jax.Array,  # (1, m * P) m full pages of token ids
    meta: jax.Array,         # (3,) int32 [pos0, full ctx_start, window's]
    ctx: tuple,              # (L, 1, KV, C, Hd): full K, V, window K, V
    tails: tuple,            # (L, 1, KV, P, Hd) as ctx (donated)
    cfg: SwaMoeConfig,
    pages: jax.Array | None = None,  # () int32: pages [0, pages) are real
):
    """Full pages of prefill as ONE program that takes their tokens through
    each layer together. A context's slot ``j`` holds position ``ctx_start
    + j`` and counts while that lies before ``pos0``; the rest is padding,
    so one program serves every full-kind context up to its length and
    every window-kind context. The chunk's own keys are attended causally,
    a window layer's band-limited. Its fresh K and V are rounded through the
    tail's type before they are attended to, as a later step will read
    them.

    Without ``pages`` the chunk is one page: returns (logits (1, P, V), the
    full tails, () int32 distinct (layer, held expert) pairs touched). With
    ``pages`` it is the m pages of ``tokens_page``, of which the first
    ``pages`` are real (the rows after them are routed to no expert and
    counted nowhere): returns (the logits of each page's last position (1,
    m, V), a page's tails for each of the m pages in order, touched)."""
    P = tails[0].shape[3]
    T = tokens_page.shape[1]
    positions = meta[0] + jnp.arange(T)
    own = jnp.tril(jnp.ones((T, T), bool))
    masks = {}
    for full, n in ((True, 0), (False, 1)):
        at_ctx = meta[1 + n] + jnp.arange(ctx[2 * n].shape[3])
        masks[full] = jnp.concatenate(
            [(at_ctx < meta[0])[None, :]
             & _in_reach(cfg, full, at_ctx[None, :], positions[:, None]),
             own & _in_reach(cfg, full, positions[None, :],
                             positions[:, None])], axis=1)
    real = (jnp.ones((T,), bool) if pages is None
            else jnp.arange(T) < pages * P)
    x = params["embed"][tokens_page[0]].astype(jnp.float32)
    new_tails = list(tails)
    fresh = [[] for _ in tails]     # each leaf's (KV, T, Hd), layer by layer
    touched = jnp.int32(0)
    for i in range(cfg.n_layers):
        _, m, full = _kind_of(cfg, i)
        box = {}

        at = _kv_at(full)

        def attend(h, i=i, m=m, full=full, at=at, box=box):
            q, k, v, gate = qkv_gate(h, params, i, positions, cfg)
            tk = k.swapaxes(0, 1).astype(tails[0].dtype)
            tv = v.swapaxes(0, 1).astype(tails[0].dtype)
            box["tails"] = (tk, tv)
            o = attend_seq(
                q, jnp.concatenate([ctx[at][m, 0], tk], axis=1),
                jnp.concatenate([ctx[at + 1][m, 0], tv], axis=1),
                masks[full], cfg)
            return gated_out(o, gate, params, i, cfg)

        x, n_hit, _ = _block(x, params, i, real, cfg, attend)
        if pages is None:
            new_tails[at] = new_tails[at].at[m, 0].set(box["tails"][0])
            new_tails[at + 1] = new_tails[at + 1].at[m, 0].set(
                box["tails"][1])
        else:
            fresh[at].append(box["tails"][0])
            fresh[at + 1].append(box["tails"][1])
        touched = touched + n_hit
    if pages is None:
        return _logits(params, x, cfg)[None], tuple(new_tails), touched
    leaves = [jnp.stack(layers)[:, None] for layers in fresh]
    made = tuple(tuple(leaf[:, :, :, j * P:(j + 1) * P] for leaf in leaves)
                 for j in range(T // P))
    return _logits(params, x[P - 1::P], cfg)[None], made, touched


def _leaf_dims(cfg: SwaMoeConfig) -> tuple:
    return (cfg.num_key_value_heads, cfg.head_dim)


def _kinds(cfg: SwaMoeConfig) -> tuple:
    return (PageKind(len(cfg.full_layers), None, 2),
            PageKind(len(cfg.window_layers), cfg.sliding_window, 2))


def _step(params, tokens, meta, n_real, pool, table, tails, cfg):
    return swa_decode_batch_step_jit(
        params, tokens, meta, np.int32(n_real), pool, table, tails, cfg)


def _page(params, tokens_page, meta, ctx, tails, cfg, pages=None):
    return swa_decode_page_jit(params, tokens_page, meta, ctx, tails, cfg,
                               pages)


@jax.jit
def swa_join_pages_jit(*kinds: tuple) -> tuple:
    """A session's pages, a kind at a time: each kind a tuple of pages, each
    page a (K, V) of (L, 1, KV, P, Hd). Joined along the token axis into the
    page program's context, K then V a kind. One program an operand count,
    whatever the pages hold."""
    return tuple(jnp.concatenate([page[i] for page in pages], axis=3)
                 for pages in kinds for i in (0, 1))


@lru_cache(maxsize=None)
def _blank_pages(cfg: SwaMoeConfig, page_tokens: int) -> tuple:
    """A page of zeros of each kind, to pad a context with (no program
    donates a context's pages), and the full kind's context of no page."""
    dt = jnp.dtype(cfg.dtype)
    full, _, window, _ = PAGED_FAMILY.leaf_shapes(cfg, page_tokens)
    return ((jnp.zeros(full, dt),) * 2, (jnp.zeros(window, dt),) * 2,
            (jnp.zeros(full[:3] + (0,) + full[4:], dt),) * 2)


def _context(pages, cfg: SwaMoeConfig, page_tokens: int) -> tuple:
    """The page program's context in ONE dispatch: the full kind's pages
    snap up to a power of two, as the delta-rule family's do, the window
    kind's to the most a session holds, both padded with blank pages that
    the program masks by position. So log(n) joins and log(n) page programs
    serve every prompt length; a concatenate a leaf would be a dispatch
    for every sixteen pages of every leaf of every chunk."""
    full, window = pages
    blank_full, blank_window, no_page = _blank_pages(cfg, page_tokens)
    to = 1 << (len(full) - 1).bit_length() if full else 0
    full = tuple(full) + (blank_full,) * (to - len(full))
    window = tuple(window) + (blank_window,) * (
        cfg.window_pages(page_tokens) - len(window))
    if not full:
        return no_page + swa_join_pages_jit(window)
    return swa_join_pages_jit(full, window)


def _write_row(pool, page, slot):
    return paged_pool_write_row_jit(*pool, *page, slot)


def _assignments_per_token(cfg: SwaMoeConfig) -> int:
    return cfg.num_experts_per_tok * cfg.n_expert_layers


# Eight pages a program: 128 tokens x the router's choices touch nearly every
# held expert of a layer, and a page program is bound by reading them at that
# length as at 16 tokens, so eight pages cost about what one does.
PAGED_FAMILY = PagedFamily(
    n_leaves=4, leaf_dims=_leaf_dims, step=_step, page=_page,
    write_row=_write_row, assignments_per_token=_assignments_per_token,
    kinds=_kinds, context=_context, chunk_pages=8,
)
