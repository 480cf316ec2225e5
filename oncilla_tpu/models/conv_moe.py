"""Gated short-convolution decoder with grouped-query attention layers among
them and routed experts without a shared one (the LFM2 shape): the family
whose carry is small enough to be kept at every prefix boundary.

Layer ``i`` is, by ``layer_types[i]``, a **gated short convolution**
(``conv``: one input projection cut in three, ``B``, ``C`` and ``z``; the
product ``B * z`` through a depthwise causal convolution of ``conv_L_cache``
taps; the result gated by ``C`` and projected out) or a grouped-query
attention layer (``full_attention``: an RMS norm a head on q and k, then
rotary over the whole head). Only the attention layers cache positions, so
a page holds ``len(attn_layers)`` layers; a convolution layer's memory is
its last ``conv_L_cache - 1`` products, whatever the context. Feed-forward
layers are :mod:`~oncilla_tpu.models.latent_moe`'s: a dense SwiGLU in the
leading ``num_dense_layers`` layers, then sigmoid-scored experts chosen
with a selection bias, their weights normalised over ``sum + 1e-6``, and no
shared expert. The output head is the embedding, transposed. Plain pre-norm
residual, float32. Mechanisms sit under the scopes ``conv``, ``attn`` and
``experts``. The equations are written out in the plain reference
(``benchmark/references/conv_gqa_moe.py``), which shares no code with this
module.

Serving: :data:`PAGED_FAMILY` is what
:class:`~oncilla_tpu.serving.engine.ServingEngine` takes from
``cfg.paged_family``: a page of a K and a V ``(La, 1, KV, P, Hd)``, a carry
of one leaf a session ``(Lc, 1, conv_L_cache - 1, D)`` float32, the fused
step (:func:`conv_decode_batch_step_jit`) and the page program
(:func:`conv_decode_page_jit`), whose context is padded to a power-of-two
number of pages and masked by position. The convolution has two forms over
the same carry: :func:`conv_step` advances every row of a batch one token,
:func:`conv_chunk` takes a page's tokens together.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.models import latent_moe as lm
from oncilla_tpu.models.kv_paging import PagedFamily, paged_pool_write_row_jit
from oncilla_tpu.models.llama import rmsnorm
from oncilla_tpu.models.swa_moe import _rotate, attend_paged, attend_seq

CONV, ATTN = "conv", "full_attention"


@dataclass(frozen=True)
class ConvMoeConfig:
    """The published ``config.json`` keys under their own names (the nested
    ``rope_parameters`` flattened to ``rope_theta``, ``layer_types`` as a
    tuple of ``num_hidden_layers`` entries), plus ``dtype``, ``head_dim``
    (the source states none: ``hidden_size / num_attention_heads``) and
    ``router_norm_eps``, the constant in the chosen scores' normalisation."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    layer_types: tuple = (CONV, CONV) + (ATTN, CONV, CONV, CONV) * 9 + (
        ATTN, CONV)
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    router_norm_eps: float = 1e-6
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types has {len(self.layer_types)} "
                             f"entries for {self.num_hidden_layers} layers")
        if set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if not self.attn_layers or not self.conv_layers:
            raise ValueError("the family needs an attention layer (its "
                             "page) and a convolution layer (its carry)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads does not divide "
                             "num_attention_heads")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache under 2 carries nothing")

    @classmethod
    def from_published(cls, conf: dict, dtype: str | None = None):
        """From a ``config.json``-shaped dict; keys this family does not
        read are ignored, and ``layer_types`` may run past
        ``num_hidden_layers`` (a cut in depth reads its head)."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in conf.items() if k in names and v is not None}
        L = kw.get("num_hidden_layers", cls.num_hidden_layers)
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"][:L])
        rope = conf.get("rope_parameters") or {}
        if "rope_theta" in rope:
            kw["rope_theta"] = float(rope["rope_theta"])
        kw.setdefault("head_dim", kw.get("hidden_size", cls.hidden_size)
                      // kw.get("num_attention_heads",
                                cls.num_attention_heads))
        kw["dtype"] = dtype or conf.get("torch_dtype", cls.dtype)
        return cls(**kw)

    def to_published(self) -> dict:
        """The inverse of :meth:`from_published`: ``rope_parameters`` a
        group again, ``layer_types`` a list, ``dtype`` as
        ``torch_dtype``."""
        d = {f.name: getattr(self, f.name) for f in fields(self)}
        d["rope_parameters"] = {"rope_theta": d.pop("rope_theta"),
                                "rope_type": "default"}
        d["layer_types"] = list(d["layer_types"])
        d["torch_dtype"] = d.pop("dtype")
        return d

    @staticmethod
    def tiny(**kw) -> "ConvMoeConfig":
        """CI size: six layers C C A C C A, one dense; 16 experts of which
        4 a token."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=6,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            layer_types=(CONV, CONV, ATTN, CONV, CONV, ATTN),
            num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
            rope_theta=100.0, max_position_embeddings=4096, dtype="float32")
        base.update(kw)
        return ConvMoeConfig(**base)

    # What the serving engine and latent_moe's shared layers read.
    @property
    def vocab(self) -> int:
        return self.vocab_size

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def first_k_dense_replace(self) -> int:
        return self.num_dense_layers

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def experts_held(self) -> tuple:
        return (0, self.num_experts)

    @property
    def n_group(self) -> int:
        return 1

    @property
    def topk_group(self) -> int:
        return 1

    @property
    def attn_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == ATTN)

    @property
    def conv_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types) if t == CONV)

    @property
    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY

    def init_params(self, key: jax.Array) -> dict:
        return init_params(key, self)


# -- parameters ----------------------------------------------------------------


def param_spec(cfg: ConvMoeConfig) -> dict:
    """{name: (shape, scale, dtype)} as :func:`latent_moe.param_spec`;
    ``scale`` None is a leaf of ones. Convolution leaves are stacked over
    the convolution layers, attention leaves over the attention layers.
    The embedding is the head too (its rows have the head's scale), and
    the selection bias is drawn, not zero: a zero bias would leave the
    choice and the weights on the same scores."""
    c = cfg
    L, D, V = c.n_layers, c.hidden_size, c.vocab
    Lc, La = len(c.conv_layers), len(c.attn_layers)
    K, Le = c.num_dense_layers, c.n_expert_layers
    F, Fe = c.intermediate_size, c.moe_intermediate_size
    Hq, KVd = c.num_attention_heads * c.head_dim, (
        c.num_key_value_heads * c.head_dim)
    w, f32 = c.dtype, "float32"

    def s_in(fan):
        return 1.0 / math.sqrt(fan)

    def s_out(fan):
        return 1.0 / math.sqrt(2 * L * fan)

    return {
        "embed": ((V, D), s_in(D), w),
        "ln_out": ((D,), None, f32),
        "ln_op": ((L, D), None, f32),
        "ln_ffn": ((L, D), None, f32),
        "conv_in": ((Lc, D, 3 * D), s_in(D), w),
        "conv_k": ((Lc, c.conv_L_cache, D), s_in(c.conv_L_cache), w),
        "conv_out": ((Lc, D, D), s_out(D), w),
        "wq": ((La, D, Hq), s_in(D), w),
        "wk": ((La, D, KVd), s_in(D), w),
        "wv": ((La, D, KVd), s_in(D), w),
        "q_norm": ((La, c.head_dim), None, f32),
        "k_norm": ((La, c.head_dim), None, f32),
        "wo": ((La, Hq, D), s_out(Hq), w),
        "w_gate": ((K, D, F), s_in(D), w),
        "w_up": ((K, D, F), s_in(D), w),
        "w_down": ((K, F, D), s_out(F), w),
        "w_router": ((Le, D, c.num_experts), s_in(D), f32),
        "e_bias": ((Le, c.num_experts), 0.1, f32),
        "w_gate_e": ((Le, c.num_experts, D, Fe), s_in(D), w),
        "w_up_e": ((Le, c.num_experts, D, Fe), s_in(D), w),
        "w_down_e": ((Le, c.num_experts, Fe, D), s_out(Fe), w),
    }


def init_params(key: jax.Array, cfg: ConvMoeConfig) -> dict:
    """Traceable (the benchmark jits it: one call on the device)."""
    spec = param_spec(cfg)
    out = {}
    for k, (name, (shape, scale, dtype)) in zip(
            jax.random.split(key, len(spec)), spec.items()):
        if scale is None:
            out[name] = jnp.ones(shape, jnp.dtype(dtype))
        else:
            out[name] = lm._normal(k, shape, scale, jnp.dtype(dtype))
    return out


# -- conv: the gated short convolution ---------------------------------------------


def _conv_gates(h, params, l: int, cfg: ConvMoeConfig):
    """h: (T, D) float32 -> the convolution's input ``B * z`` and the
    output gate ``C``, both (T, D) float32."""
    bcz = lm._dot(h, params["conv_in"][l], "td,de->te", jnp.dtype(cfg.dtype))
    b, c, z = jnp.split(bcz, 3, axis=-1)
    return b * z, c


def _conv_out(c, mixed, params, l: int, cfg: ConvMoeConfig):
    return lm._dot(c * mixed, params["conv_out"][l], "td,de->te",
                   jnp.dtype(cfg.dtype))


def conv_step(h, carry, params, l: int, cfg: ConvMoeConfig):
    """One token a row. h: (B, D) float32; carry: (B, K - 1, D), each row's
    last ``K - 1`` products, the oldest first. Returns (y (B, D), the carry
    rolled by one)."""
    v, c = _conv_gates(h, params, l, cfg)
    window = jnp.concatenate([carry, v[:, None, :]], axis=1)
    taps = params["conv_k"][l].astype(jnp.float32)
    mixed = (window * taps[None]).sum(axis=1)
    return _conv_out(c, mixed, params, l, cfg), window[:, 1:]


def conv_chunk(h, carry, params, l: int, cfg: ConvMoeConfig):
    """A chunk of T tokens of ONE sequence together. h: (T, D); carry:
    (K - 1, D), the products of the ``K - 1`` tokens before the chunk (zeros
    before the sequence's first). Returns (y (T, D), the last ``K - 1``
    products at the chunk's end: for a chunk shorter than that, what is
    left of the carry before them)."""
    T = h.shape[0]
    v, c = _conv_gates(h, params, l, cfg)
    seq = jnp.concatenate([carry, v], axis=0)
    taps = params["conv_k"][l].astype(jnp.float32)
    mixed = sum(taps[j][None] * seq[j:j + T]
                for j in range(cfg.conv_L_cache))
    return _conv_out(c, mixed, params, l, cfg), seq[T:]


# -- attn: grouped queries, a norm a head on q and k -------------------------------


@lru_cache(maxsize=None)
def _inv_freq(cfg: ConvMoeConfig) -> np.ndarray:
    exponent = np.arange(0, cfg.head_dim, 2, dtype=np.float64) / cfg.head_dim
    return (1.0 / cfg.rope_theta ** exponent).astype(np.float32)


def qkv(h, params, m: int, positions, cfg: ConvMoeConfig):
    """h: (T, D) float32 -> q (T, H, Hd) and k (T, KV, Hd), normed a head
    and rotated, and v (T, KV, Hd), all float32."""
    dt = jnp.dtype(cfg.dtype)
    T, hd = h.shape[0], cfg.head_dim
    q = lm._dot(h, params["wq"][m], "td,da->ta", dt).reshape(T, -1, hd)
    k = lm._dot(h, params["wk"][m], "td,da->ta", dt).reshape(T, -1, hd)
    v = lm._dot(h, params["wv"][m], "td,da->ta", dt).reshape(T, -1, hd)
    q = rmsnorm(q, params["q_norm"][m], cfg.norm_eps)
    k = rmsnorm(k, params["k_norm"][m], cfg.norm_eps)
    inv_freq = _inv_freq(cfg)
    return (_rotate(q, positions, inv_freq, 1.0),
            _rotate(k, positions, inv_freq, 1.0), v)


def attn_out(o, params, m: int, cfg: ConvMoeConfig):
    return lm._dot(o.reshape(o.shape[0], -1), params["wo"][m], "ta,ad->td",
                   jnp.dtype(cfg.dtype))


# -- the layers ----------------------------------------------------------------------


def _block(x, params, i: int, real, cfg: ConvMoeConfig, operator):
    """Pre-norm residual layer ``i``: ``operator(h)`` (the convolution or
    the attention) then the FFN. Returns (x, distinct experts touched,
    chosen experts | None)."""
    scope = "conv" if cfg.layer_types[i] == CONV else "attn"
    with jax.named_scope(scope):
        x = x + operator(rmsnorm(x, params["ln_op"][i], cfg.norm_eps))
    y, (n_hit, idx) = lm._ffn(rmsnorm(x, params["ln_ffn"][i], cfg.norm_eps),
                              params, i, real, cfg)
    return x + y, n_hit, idx


def _logits(params, x, cfg: ConvMoeConfig):
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return lm._dot(x, params["embed"], "td,vd->tv", jnp.dtype(cfg.dtype))


def forward(params: dict, tokens: jax.Array, cfg: ConvMoeConfig,
            return_routing: bool = False):
    """Logits (B, S, V) float32 of a token batch, every position attending
    causally and every convolution starting from zeros: no cache, no carry.
    With ``return_routing`` also the experts chosen, (expert layers, B, S,
    k)."""
    B, S = tokens.shape
    positions = jnp.tile(jnp.arange(S), B)
    causal = jnp.tril(jnp.ones((S, S), bool))
    real = jnp.ones((B * S,), bool)
    zeros = jnp.zeros((cfg.conv_L_cache - 1, cfg.hidden_size), jnp.float32)
    x = params["embed"][tokens.reshape(-1)].astype(jnp.float32)
    routing = []
    for i in range(cfg.n_layers):
        if cfg.layer_types[i] == CONV:
            def operator(h, l=cfg.conv_layers.index(i)):
                y = jax.vmap(lambda a: conv_chunk(a, zeros, params, l,
                                                  cfg)[0])(h.reshape(B, S, -1))
                return y.reshape(B * S, -1)
        else:
            def operator(h, m=cfg.attn_layers.index(i)):
                q, k, v = (a.reshape((B, S) + a.shape[1:])
                           for a in qkv(h, params, m, positions, cfg))
                o = jax.vmap(lambda a, b, c: attend_seq(
                    a, b.swapaxes(0, 1), c.swapaxes(0, 1), causal, cfg))(
                        q, k, v)
                return attn_out(o.reshape((B * S,) + o.shape[2:]), params, m,
                                cfg)

        x, _, idx = _block(x, params, i, real, cfg, operator)
        if idx is not None:
            routing.append(idx.reshape(B, S, -1))
    logits = _logits(params, x, cfg).reshape(B, S, -1)
    return (logits, jnp.stack(routing)) if return_routing else logits


# -- the paged programs --------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("tails", "carry"))
def conv_decode_batch_step_jit(
    params: dict,
    tokens: jax.Array,     # (B,) current token ids, one per session
    meta: jax.Array,       # (B, 4) int32 [pos, tail_len, ctx_len, -]
    n_real: jax.Array,     # () int32: rows [0, n_real) are sessions
    pool: tuple,           # K and V rows, each (N, La, KV, P, Hd)
    table: jax.Array,      # (B, MP) int32 pool row per context page
    tails: tuple,          # K and V tails, each (La, B, KV, P, Hd) (donated)
    carry: jax.Array,      # (Lc, B, K - 1, D) float32 (donated)
    cfg: ConvMoeConfig,
):
    """ONE fused decode step for a batch of sessions: the attention layers
    over the block table as ``swa_moe.swa_decode_batch_step_jit`` reads its
    full kind (padded slots gather row 0 and are masked by ``ctx_len``, the
    same tail insertion, a row with ``tail_len`` 0 reading its tail as
    zeros), the convolution layers each row from its own carry. Rows at and
    past ``n_real`` are padding: routed to no expert, counted nowhere, their
    carry left as it was. Returns (logits (B, V) float32, new tails, new
    carry, () int32 distinct (layer, expert) pairs that received a real
    token)."""
    pos, tail_len, ctx_len = meta[:, 0], meta[:, 1], meta[:, 2]
    B = tokens.shape[0]
    P = tails[0].shape[3]
    real = jnp.arange(B) < n_real
    mask_ctx = jnp.arange(table.shape[1] * P)[None, :] < ctx_len[:, None]
    mask_tail = jnp.arange(P)[None, :] <= tail_len[:, None]
    slot = (jnp.arange(P)[None, :] == tail_len[:, None])[:, None, :, None]
    live = (tail_len > 0)[:, None, None, None]
    tail_k, tail_v = tails
    x = params["embed"][tokens].astype(jnp.float32)
    touched = jnp.int32(0)
    for i in range(cfg.n_layers):
        box = {}
        if cfg.layer_types[i] == CONV:
            def operator(h, l=cfg.conv_layers.index(i), box=box):
                y, c = conv_step(h, carry[l], params, l, cfg)
                box["carry"] = (l, jnp.where(real[:, None, None], c,
                                             carry[l]))
                return y
        else:
            def operator(h, m=cfg.attn_layers.index(i), box=box):
                q, k, v = qkv(h, params, m, pos, cfg)
                tk = jnp.where(slot, k[:, :, None, :].astype(tail_k.dtype),
                               jnp.where(live, tail_k[m], 0))
                tv = jnp.where(slot, v[:, :, None, :].astype(tail_v.dtype),
                               jnp.where(live, tail_v[m], 0))
                box["tails"] = (m, tk, tv)
                o = attend_paged(
                    q, jnp.take(pool[0][:, m], table, axis=0),
                    jnp.take(pool[1][:, m], table, axis=0), tk, tv,
                    mask_ctx, mask_tail, cfg)
                return attn_out(o, params, m, cfg)

        x, n_hit, _ = _block(x, params, i, real, cfg, operator)
        if "carry" in box:
            l, c = box["carry"]
            carry = carry.at[l].set(c)
        else:
            m, tk, tv = box["tails"]
            tail_k, tail_v = tail_k.at[m].set(tk), tail_v.at[m].set(tv)
        touched = touched + n_hit
    return _logits(params, x, cfg), (tail_k, tail_v), carry, touched


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("tails", "carry"))
def conv_decode_page_jit(
    params: dict,
    tokens_page: jax.Array,  # (1, P) one full page of token ids
    meta: jax.Array,         # (2,) int32 [pos0, -]
    ctx: tuple,              # K and V, each (La, 1, KV, C, Hd); slots at and
                             #  past pos0 are padding
    tails: tuple,            # K and V tails, each (La, 1, KV, P, Hd) (donated)
    carry: jax.Array,        # (Lc, 1, K - 1, D) float32 (donated)
    cfg: ConvMoeConfig,
):
    """One full page of prefill as ONE program that takes the page's P
    tokens through each layer together: attention over the context's first
    ``pos0`` positions (the rest of ``ctx`` is padding, so one program
    serves every context up to its length) and, causally, the page's own
    keys, rounded through the tail's type as a later step will read them;
    the convolution layers chunk-wise from the carry. Returns (logits (1, P,
    V), the full tails, new carry, () int32 distinct (layer, expert) pairs
    touched)."""
    P = tails[0].shape[3]
    C = ctx[0].shape[3]
    positions = meta[0] + jnp.arange(P)
    mask = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(C)[None, :] < meta[0], (P, C)),
         jnp.tril(jnp.ones((P, P), bool))], axis=1)
    real = jnp.ones((P,), bool)
    tail_k, tail_v = tails
    x = params["embed"][tokens_page[0]].astype(jnp.float32)
    touched = jnp.int32(0)
    for i in range(cfg.n_layers):
        box = {}
        if cfg.layer_types[i] == CONV:
            def operator(h, l=cfg.conv_layers.index(i), box=box):
                y, c = conv_chunk(h, carry[l, 0], params, l, cfg)
                box["carry"] = (l, c)
                return y
        else:
            def operator(h, m=cfg.attn_layers.index(i), box=box):
                q, k, v = qkv(h, params, m, positions, cfg)
                tk = k.swapaxes(0, 1).astype(tail_k.dtype)
                tv = v.swapaxes(0, 1).astype(tail_v.dtype)
                box["tails"] = (m, tk, tv)
                o = attend_seq(
                    q, jnp.concatenate([ctx[0][m, 0], tk], axis=1),
                    jnp.concatenate([ctx[1][m, 0], tv], axis=1), mask, cfg)
                return attn_out(o, params, m, cfg)

        x, n_hit, _ = _block(x, params, i, real, cfg, operator)
        if "carry" in box:
            l, c = box["carry"]
            carry = carry.at[l, 0].set(c)
        else:
            m, tk, tv = box["tails"]
            tail_k = tail_k.at[m, 0].set(tk)
            tail_v = tail_v.at[m, 0].set(tv)
        touched = touched + n_hit
    return _logits(params, x, cfg)[None], (tail_k, tail_v), carry, touched


def _leaf_dims(cfg: ConvMoeConfig) -> tuple:
    return (cfg.num_key_value_heads, cfg.head_dim)


def _cached_layers(cfg: ConvMoeConfig) -> int:
    return len(cfg.attn_layers)


def _carry_leaves(cfg: ConvMoeConfig, batch: int = 1) -> tuple:
    return (((len(cfg.conv_layers), batch, cfg.conv_L_cache - 1,
              cfg.hidden_size), jnp.float32),)


def _step(params, tokens, meta, n_real, pool, table, tails, cfg, carry):
    logits, tails, state, touched = conv_decode_batch_step_jit(
        params, tokens, meta, np.int32(n_real), pool, table, tails, carry[0],
        cfg)
    return logits, tails, touched, (state,)


def _page(params, tokens_page, meta, ctx, tails, cfg, carry):
    logits, tails, state, touched = conv_decode_page_jit(
        params, tokens_page, meta, ctx, tails, carry[0], cfg)
    return logits, tails, touched, (state,)


@jax.jit
def conv_join_pages_jit(*pages: tuple) -> tuple:
    """A session's pages, each a (K, V) of (La, 1, KV, P, Hd), joined along
    the token axis into the page program's context. One program an operand
    count, whatever the pages hold."""
    return tuple(jnp.concatenate([page[i] for page in pages], axis=3)
                 for i in (0, 1))


@lru_cache(maxsize=None)
def _blank_page(cfg: ConvMoeConfig, page_tokens: int) -> tuple:
    """A page of zeros to pad a context with (no program donates a
    context's pages), and the context of no page."""
    dt = jnp.dtype(cfg.dtype)
    shape = PAGED_FAMILY.leaf_shape(cfg, page_tokens)
    return ((jnp.zeros(shape, dt),) * 2,
            (jnp.zeros(shape[:3] + (0,) + shape[4:], dt),) * 2)


def _context(pages, cfg: ConvMoeConfig, page_tokens: int) -> tuple:
    """The page program's context in ONE dispatch: the pages snap up to a
    power of two, as the delta-rule and window families' do, padded with
    blank pages that the program masks by position. So log(n) joins and
    log(n) page programs serve every prompt length."""
    held = pages[0]
    blank, no_page = _blank_page(cfg, page_tokens)
    if not held:
        return no_page
    to = 1 << (len(held) - 1).bit_length()
    return conv_join_pages_jit(*held, *(blank,) * (to - len(held)))


def _write_row(pool, page, slot):
    return paged_pool_write_row_jit(*pool, *page, slot)


def _assignments_per_token(cfg: ConvMoeConfig) -> int:
    return cfg.num_experts_per_tok * cfg.n_expert_layers


PAGED_FAMILY = PagedFamily(
    n_leaves=2, leaf_dims=_leaf_dims, step=_step, page=_page,
    write_row=_write_row, assignments_per_token=_assignments_per_token,
    cached_layers=_cached_layers, carry_leaves=_carry_leaves,
    context=_context,
)
