"""Latent-attention, routed-expert, hyper-connection decoder family (the
Xing4.0 / DeepSeek-V3 shape): the third model family beside
:mod:`~oncilla_tpu.models.llama` and :mod:`~oncilla_tpu.models.moe`, and the
first whose page is not a K and a V.

Three mechanisms, each under its own ``jax.named_scope``:

- ``mla`` — multi-head latent attention. The cache holds, per position and
  layer, ONE vector shared by every head: the normed KV latent
  (``kv_lora_rank``) followed by the rotated shared rope key
  (``qk_rope_head_dim``). A decode step *absorbs* the key half of ``wkv_b``
  into the query and applies its value half after the softmax, so it reads
  the latent as it lies in the page; a page's prefill *expands* K and V.
- ``experts`` — sigmoid-scored top-k routing (softmax-scored where a
  config's ``scoring_func`` says so) over ``n_routed_experts`` plus
  shared experts, dropless: a loop over the DISTINCT experts that received a
  real token, each read once (a dynamic slice of the stacked expert
  weights) and applied to every row with that row's weight (zero where it
  was not chosen). Padded batch rows are routed nowhere. The layer hands
  back how many distinct experts it touched. Two things a config may say
  and ``LatentMoeConfig`` leaves at "no": a group limit on the choice
  (``n_group``, ``topk_group``) and a held range (``experts_held``), the
  experts whose weights this chip has: the layer routes over all of them
  and computes its own experts' part (``models/kda_latent.py`` uses both).
- ``mhc`` — manifold-constrained hyper-connections: the residual state is
  ``hc_mult`` streams, every sub-layer reads a sigmoid-gated mix of them
  and writes back through a Sinkhorn-normalised stream-mixing matrix. The
  coefficient path, the streams and the router are float32; the large
  products take ``cfg.dtype`` inputs and accumulate in float32.

The equations are written out in the plain reference the benchmark holds
this module to (``benchmark/references/latent_moe_hc.py``), which shares no
code with it.

Serving: :data:`PAGED_FAMILY` is what
:class:`~oncilla_tpu.serving.engine.ServingEngine` takes from
``cfg.paged_family``: a page of one latent leaf ``(L, 1, 1, P, W)``, the
fused batch step over a page pool and block table
(:func:`latent_decode_batch_step_jit`) and the page program
(:func:`latent_decode_page_jit`), which, as the dense family's
(``kv_paging.paged_decode_page_jit``) does, takes a page's tokens through
each layer together.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.models.kv_paging import PagedFamily
from oncilla_tpu.models.llama import rmsnorm


@dataclass(frozen=True)
class LatentMoeConfig:
    """The published ``config.json`` keys under their own names (the nested
    ``rope_scaling`` group flattened to ``rope_*``), plus ``dtype``."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    max_position_embeddings: int = 262144
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    @classmethod
    def from_published(cls, conf: dict, dtype: str | None = None):
        """From a ``config.json``-shaped dict; keys this family does not
        read are ignored."""
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in conf.items() if k in names}
        for k, v in (conf.get("rope_scaling") or {}).items():
            if f"rope_{k}" in names:
                kw[f"rope_{k}"] = v
        kw["dtype"] = dtype or conf.get("torch_dtype", cls.dtype)
        return cls(**kw)

    def to_published(self) -> dict:
        """The inverse of :meth:`from_published`: the ``config.json`` keys,
        ``rope_scaling`` a group again and ``dtype`` as ``torch_dtype``."""
        d = {f.name: getattr(self, f.name) for f in fields(self)}
        d["rope_scaling"] = {k[5:]: d.pop(k) for k in list(d)
                             if k.startswith("rope_") and k != "rope_theta"}
        d["torch_dtype"] = d.pop("dtype")
        return d

    @staticmethod
    def tiny() -> "LatentMoeConfig":
        """CI size: every width shrunk, four streams, 8 experts top-2, one
        dense and two expert layers."""
        return LatentMoeConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            intermediate_size=96, first_k_dense_replace=1,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, max_position_embeddings=4096,
            rope_factor=4.0, rope_original_max_position_embeddings=64,
            dtype="float32",
        )

    # What the serving engine reads of any config.
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
    def head_dim(self) -> int:
        """What one head hands to the output projection."""
        return self.v_head_dim

    @property
    def latent_width(self) -> int:
        """Values a position a layer holds in the cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def experts_held(self) -> tuple:
        """(first, count) of the routed experts this chip holds: all."""
        return (0, self.n_routed_experts)

    @property
    def paged_family(self) -> PagedFamily:
        return PAGED_FAMILY

    def init_params(self, key: jax.Array) -> dict:
        """:func:`init_params` of this config (what a caller that holds
        only the config reaches)."""
        return init_params(key, self)


# -- parameters ----------------------------------------------------------------


def param_spec(cfg: LatentMoeConfig) -> dict:
    """{name: (shape, scale, dtype)}; ``scale`` None is a constant leaf
    (``fill``: ones for gains and gates, zeros for biases). Projections are
    scaled normals, ``1/sqrt(fan_in)``, the ones that write the residual
    ``1/sqrt(2 L fan_in)``, in ``cfg.dtype``; gains, the router and the
    hyper-connection coefficients are float32."""
    c = cfg
    L, D, H, V = c.n_layers, c.hidden_size, c.num_attention_heads, c.vocab
    K, Le, E = c.first_k_dense_replace, c.n_expert_layers, c.n_routed_experts
    F, Fe = c.intermediate_size, c.moe_intermediate_size
    Fs = Fe * c.n_shared_experts
    n, nD = c.hc_mult, c.hc_mult * c.hidden_size
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
        "wq_a": ((L, D, c.q_lora_rank), s_in(D), w),
        "q_norm": ((L, c.q_lora_rank), None, f32),
        "wq_b": ((L, c.q_lora_rank,
                  H * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                 s_in(c.q_lora_rank), w),
        "wkv_a": ((L, D, c.latent_width), s_in(D), w),
        "kv_norm": ((L, c.kv_lora_rank), None, f32),
        "wkv_b": ((L, c.kv_lora_rank,
                   H * (c.qk_nope_head_dim + c.v_head_dim)),
                  s_in(c.kv_lora_rank), w),
        "wo": ((L, H * c.v_head_dim, D), s_out(H * c.v_head_dim), w),
        # Hyper-connections, one set a sub-layer (attention, FFN): the gain
        # over vec(X), phi's columns [pre n | post n | res n*n], its bias
        # and the three gates.
        "hc_norm": ((L, 2, nD), None, f32),
        "hc_phi": ((L, 2, nD, 2 * n + n * n), s_in(nD), f32),
        "hc_b": ((L, 2, 2 * n + n * n), None, f32),
        "hc_alpha": ((L, 2, 3), None, f32),
        "w_gate": ((K, D, F), s_in(D), w),
        "w_up": ((K, D, F), s_in(D), w),
        "w_down": ((K, F, D), s_out(F), w),
        "w_router": ((Le, D, E), s_in(D), f32),
        "e_bias": ((Le, E), None, f32),
        "w_gate_e": ((Le, E, D, Fe), s_in(D), w),
        "w_up_e": ((Le, E, D, Fe), s_in(D), w),
        "w_down_e": ((Le, E, Fe, D), s_out(Fe), w),
        "ws_gate": ((Le, D, Fs), s_in(D), w),
        "ws_up": ((Le, D, Fs), s_in(D), w),
        "ws_down": ((Le, Fs, D), s_out(Fs), w),
    }


_ZERO_LEAVES = ("hc_b", "e_bias")
# Elements of a leaf drawn at a time: the float32 draw of a whole expert
# leaf (1.2 G values) would not fit beside the weights already made.
_INIT_CHUNK = 1 << 23


def _normal(key, shape, scale, dtype):
    """Scaled normal of ``shape``, drawn over leading axes in chunks."""
    size = math.prod(shape)
    lead, chunks = 0, 1
    while size // chunks > _INIT_CHUNK and lead < len(shape) - 1:
        chunks *= shape[lead]
        lead += 1
    if chunks == 1:
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            dtype)
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                   * scale).astype(dtype),
        jax.random.split(key, chunks))
    return out.reshape(shape)


def init_params(key: jax.Array, cfg: LatentMoeConfig) -> dict:
    """Traceable (the benchmark jits it: one call on the device)."""
    spec = param_spec(cfg)
    out = {}
    for k, (name, (shape, scale, dtype)) in zip(
            jax.random.split(key, len(spec)), spec.items()):
        if scale is None:
            fill = jnp.zeros if name in _ZERO_LEAVES else jnp.ones
            out[name] = fill(shape, jnp.dtype(dtype))
        else:
            out[name] = _normal(k, shape, scale, jnp.dtype(dtype))
    return out


# -- mhc: the residual streams -------------------------------------------------


def sinkhorn(logits, iters: int, eps: float, lo: float, hi: float):
    """exp of the clamped logits, rows and columns normalised in turn."""
    m = jnp.exp(jnp.clip(logits, lo, hi))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hc_coefficients(X, params, layer: int, sub: int, cfg: LatentMoeConfig):
    """X: (T, n, D) float32 -> Hpre (T, n), Hpost (T, n), Hres (T, n, n)."""
    n = cfg.hc_mult
    T = X.shape[0]
    xt = rmsnorm(X.reshape(T, -1), params["hc_norm"][layer, sub],
                 cfg.rms_norm_eps)
    z = jnp.einsum("td,dc->tc", xt, params["hc_phi"][layer, sub],
                   precision=jax.lax.Precision.HIGHEST)
    a, b = params["hc_alpha"][layer, sub], params["hc_b"][layer, sub]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn(
        a[2] * z[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n),
        cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.mhc_h_res_clamp_min,
        cfg.mhc_h_res_clamp_max)
    return pre, post, res


def _sublayer(X, params, layer: int, sub: int, cfg: LatentMoeConfig, fn):
    """``X' = Hres X + outer(Hpost, F(RMSNorm(Hpre X)))``; ``fn`` takes the
    float32 (T, D) input and returns (float32 (T, D), aux)."""
    with jax.named_scope("mhc"):
        pre, post, res = hc_coefficients(X, params, layer, sub, cfg)
        ln = params["ln_attn" if sub == 0 else "ln_mlp"][layer]
        # Sums over the n streams, elementwise: a float32 einsum would go
        # through the MXU in bf16 passes.
        h = rmsnorm((pre[:, :, None] * X).sum(axis=1), ln, cfg.rms_norm_eps)
    y, aux = fn(h)
    with jax.named_scope("mhc"):
        X = ((res[:, :, :, None] * X[:, None, :, :]).sum(axis=2)
             + post[:, :, None] * y[:, None, :])
    return X, aux


# -- mla: latent attention -------------------------------------------------------


def yarn_inv_freq(cfg: LatentMoeConfig) -> np.ndarray:
    """YaRN's blend of plain and interpolated rotary frequencies."""
    dr, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    exponent = np.arange(0, dr, 2, dtype=np.float64) / dr
    extra = 1.0 / theta ** exponent
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)     # no scaling: plain rotary
    inter = extra / cfg.rope_factor

    def correction_dim(rotations):
        return (dr * math.log(cfg.rope_original_max_position_embeddings
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: LatentMoeConfig) -> float:
    m = (0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
         if cfg.rope_factor > 1 else 1.0)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rotate(x, positions, cfg: LatentMoeConfig):
    """x: (T, ..., dr) float32; positions: (T,). Adjacent pairs."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _dot(a, b, spec: str, dtype):
    """A large product: inputs in the served type, float32 out."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def latent_qkv(h, params, layer: int, positions, cfg: LatentMoeConfig):
    """h: (T, D) float32 -> q_nope (T, H, dn), rotated q_rope (T, H, dr)
    and this position's cache entry (T, W): normed latent | rotated key.
    A config whose ``q_lora_rank`` is null has one query matrix ``wq``."""
    dt = jnp.dtype(cfg.dtype)
    H, dn, R = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    if cfg.q_lora_rank:
        cq = rmsnorm(_dot(h, params["wq_a"][layer], "td,dr->tr", dt),
                     params["q_norm"][layer], eps)
        q = _dot(cq, params["wq_b"][layer], "tr,rh->th", dt)
    else:
        q = _dot(h, params["wq"][layer], "td,dh->th", dt)
    q = q.reshape(h.shape[0], H, -1)
    kva = _dot(h, params["wkv_a"][layer], "td,dw->tw", dt)
    ckv = rmsnorm(kva[:, :R], params["kv_norm"][layer], eps)
    k_rope = _rotate(kva[:, R:], positions, cfg)
    entry = jnp.concatenate([ckv, k_rope], axis=-1)
    return q[..., :dn], _rotate(q[..., dn:], positions, cfg), entry


def _wkv_b(params, layer: int, cfg: LatentMoeConfig):
    """(R, H, dn) key half and (R, H, dv) value half of ``wkv_b``."""
    w = params["wkv_b"][layer].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def attend_absorbed(q_nope, q_rope, latent, mask, params, layer: int,
                    cfg: LatentMoeConfig):
    """Decode form. q_*: (B, H, .); latent: (B, C, W) cache entries, one row
    a session; mask: (B, C). The key half of ``wkv_b`` goes into the query,
    the value half after the softmax: the cache is read as it lies."""
    dt = jnp.dtype(cfg.dtype)
    R = cfg.kv_lora_rank
    wk, wv = _wkv_b(params, layer, cfg)
    ckv, k_rope = latent[..., :R], latent[..., R:]
    q_lat = _dot(q_nope, wk, "bhd,rhd->bhr", dt)
    s = (_dot(q_lat, ckv, "bhr,bcr->bhc", dt)
         + _dot(q_rope, k_rope, "bhd,bcd->bhc", dt)) * softmax_scale(cfg)
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1)
    o_lat = _dot(p, ckv, "bhc,bcr->bhr", dt)
    o = _dot(o_lat, wv, "bhr,rhd->bhd", dt)
    return _dot(o.reshape(o.shape[0], -1), params["wo"][layer],
                "ta,ad->td", dt)


def attend_expanded(q_nope, q_rope, latent, mask, params, layer: int,
                    cfg: LatentMoeConfig):
    """Prefill form, one sequence. q_*: (S, H, .); latent: (C, W); mask:
    (S, C). K and V are expanded from the latent for every head."""
    dt = jnp.dtype(cfg.dtype)
    R = cfg.kv_lora_rank
    wk, wv = _wkv_b(params, layer, cfg)
    ckv, k_rope = latent[:, :R], latent[:, R:]
    k_nope = _dot(ckv, wk, "cr,rhd->chd", dt)
    v = _dot(ckv, wv, "cr,rhd->chd", dt)
    s = (_dot(q_nope, k_nope, "shd,chd->hsc", dt)
         + _dot(q_rope, k_rope, "shd,cd->hsc", dt)) * softmax_scale(cfg)
    p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
    o = _dot(p, v, "hsc,chd->shd", dt)
    return _dot(o.reshape(o.shape[0], -1), params["wo"][layer],
                "ta,ad->td", dt)


# -- experts ---------------------------------------------------------------------


def _swiglu(h, w_gate, w_up, w_down, dt):
    act = (jax.nn.silu(_dot(h, w_gate, "td,df->tf", dt))
           * _dot(h, w_up, "td,df->tf", dt))
    return _dot(act, w_down, "tf,fd->td", dt)


def _group_limit(pick, n_group: int, topk_group: int):
    """pick: (T, E) scores the choice is made on. A group's score is the
    sum of its best two; outside the best ``topk_group`` of the
    ``n_group`` groups every score becomes -inf."""
    T, E = pick.shape
    g = pick.reshape(T, n_group, E // n_group)
    score = jax.lax.top_k(g, 2)[0].sum(-1)
    _, keep = jax.lax.top_k(score, topk_group)
    kept = jax.nn.one_hot(keep, n_group, dtype=bool).any(axis=1)
    return jnp.where(kept[:, :, None], g, -jnp.inf).reshape(T, E)


def route(h, params, j: int, real, cfg):
    """h: (T, D) float32; real: (T,) bool. Returns the chosen experts
    (T, k), the (T, E) weight of every expert for every row (zero where it
    was not chosen, and everywhere in a row that is padding) and which
    experts (E,) a real row chose.

    Two score functions (``cfg.scoring_func``, sigmoid where a config
    states none): ``sigmoid`` chooses on the scores plus the selection bias
    ``e_bias``, group-limited with ``cfg.n_group`` over 1
    (:func:`_group_limit`), and weighs the chosen scores normalised and
    times ``routed_scaling_factor`` (a config that states a
    ``router_norm_eps`` adds it to the sum they are divided by);
    ``softmax`` chooses the ``k`` largest probabilities of a softmax over
    every router output and weighs them normalised, with no bias and no
    scaling."""
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    logits = jnp.einsum("td,de->te", h, params["w_router"][j],
                        precision=jax.lax.Precision.HIGHEST)
    if getattr(cfg, "scoring_func", "sigmoid") == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(p, k)
        chosen = jnp.take_along_axis(p, idx, axis=-1)
        w = chosen / chosen.sum(-1, keepdims=True)
    else:
        w, idx = _sigmoid_choice(logits, params, j, k, cfg)
    sel = jax.nn.one_hot(idx, E, dtype=bool) & real[:, None, None]
    weights = jnp.where(sel, w[:, :, None], 0.0).sum(axis=1)
    return idx, weights, sel.any(axis=(0, 1))


def _sigmoid_choice(logits, params, j: int, k: int, cfg):
    """The sigmoid router's (weights (T, k), chosen (T, k))."""
    s = jax.nn.sigmoid(logits)
    pick = s + params["e_bias"][j]
    if cfg.n_group > 1:
        pick = _group_limit(pick, cfg.n_group, cfg.topk_group)
    _, idx = jax.lax.top_k(pick, k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    # Traced in this order (the scaling, the sum, the division): what the
    # families without the constant have always compiled.
    scaled = cfg.routed_scaling_factor * chosen
    total = chosen.sum(-1, keepdims=True)
    if getattr(cfg, "router_norm_eps", 0.0):
        total = total + cfg.router_norm_eps
    return scaled / total, idx


def expert_ffn(h, params, j: int, real, cfg):
    """Expert layer ``j`` (of the layers that have experts). Dropless: the
    loop runs once for each DISTINCT expert that a real row chose, reads
    that expert's three matrices once, and adds its output to every row
    under that row's weight. The layer routes over all
    ``cfg.n_routed_experts`` and computes the part of the result its own
    experts give: ``cfg.experts_held`` is the (first, count) range whose
    weights the stacked leaves hold; a row whose experts all live
    elsewhere gets the shared expert alone, where the family has one (its
    ``ws_*`` leaves). The choice (scores, top-k, weights) is under the
    scope ``router``. Returns (y (T, D) float32, distinct held experts
    touched () int32, chosen experts (T, k))."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("router"):
        idx, weights, hit = route(h, params, j, real, cfg)
    first, held = cfg.experts_held
    if (first, held) != (0, cfg.n_routed_experts):
        weights = weights[:, first:first + held]
        hit = hit[first:first + held]
    n_hit = hit.sum().astype(jnp.int32)
    order = jnp.argsort(~hit, stable=True)      # touched experts first
    x = h.astype(dt)

    def expert(name, e):
        # One dynamic slice of the whole stacked leaf: a static slice of
        # layer j first could be materialised, all experts of it.
        w = params[name]
        return jax.lax.dynamic_slice(
            w, (j, e, 0, 0), (1, 1) + w.shape[2:])[0, 0]

    def body(r, acc):
        e = order[r]
        y = _swiglu(x, expert("w_gate_e", e), expert("w_up_e", e),
                    expert("w_down_e", e), dt)
        return acc + weights[:, e][:, None] * y

    y = jax.lax.fori_loop(0, n_hit, body, jnp.zeros(h.shape, jnp.float32))
    if "ws_gate" in params:
        y = y + _swiglu(x, params["ws_gate"][j], params["ws_up"][j],
                        params["ws_down"][j], dt)
    return y, n_hit, idx


def _ffn(h, params, layer: int, real, cfg):
    """The FFN sub-layer of ``layer``: (y, experts touched, chosen | None)."""
    K = cfg.first_k_dense_replace
    if layer < K:
        dt = jnp.dtype(cfg.dtype)
        y = _swiglu(h, params["w_gate"][layer], params["w_up"][layer],
                    params["w_down"][layer], dt)
        return y, (jnp.int32(0), None)
    with jax.named_scope("experts"):
        y, n_hit, idx = expert_ffn(h, params, layer - K, real, cfg)
    return y, (n_hit, idx)


def _embed_streams(params, tokens, cfg: LatentMoeConfig):
    x = params["embed"][tokens].astype(jnp.float32)
    return jnp.repeat(x[:, None, :], cfg.hc_mult, axis=1)


def _logits(params, X, cfg: LatentMoeConfig):
    x = rmsnorm(X.sum(axis=1), params["ln_out"], cfg.rms_norm_eps)
    return _dot(x, params["lm_head"], "td,dv->tv", jnp.dtype(cfg.dtype))


# -- the plain forward -------------------------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: LatentMoeConfig,
            return_routing: bool = False):
    """Logits (B, S, V) float32 of a token batch, every position attending
    causally to what precedes it: no cache, expanded attention. With
    ``return_routing`` also the experts chosen, (expert layers, B, S, k)."""
    B, S = tokens.shape
    positions = jnp.tile(jnp.arange(S), B)
    causal = jnp.tril(jnp.ones((S, S), bool))
    real = jnp.ones((B * S,), bool)
    X = _embed_streams(params, tokens.reshape(-1), cfg)
    routing = []
    for i in range(cfg.n_layers):
        def attention(h, i=i):
            with jax.named_scope("mla"):
                qn, qr, entry = (
                    a.reshape((B, S) + a.shape[1:])
                    for a in latent_qkv(h, params, i, positions, cfg))
                y = jax.vmap(lambda a, b, c: attend_expanded(
                    a, b, c, causal, params, i, cfg))(qn, qr, entry)
            return y.reshape(B * S, -1), None

        X, _ = _sublayer(X, params, i, 0, cfg, attention)
        X, (_, idx) = _sublayer(
            X, params, i, 1, cfg, lambda h, i=i: _ffn(h, params, i, real, cfg))
        if idx is not None:
            routing.append(idx.reshape(B, S, -1))
    logits = _logits(params, X, cfg).reshape(B, S, -1)
    return (logits, jnp.stack(routing)) if return_routing else logits


# -- the paged programs --------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(6,))
def latent_decode_batch_step_jit(
    params: dict,
    tokens: jax.Array,     # (B,) current token ids, one per session
    meta: jax.Array,       # (B, 4) int32 [pos, tail_len, ctx_len, -]
    n_real: jax.Array,     # () int32: rows [0, n_real) are sessions
    pool: jax.Array,       # (N, L, 1, P, W) resident page pool
    table: jax.Array,      # (B, MP) int32 pool row per context page
    tail: jax.Array,       # (L, B, 1, P, W) per-session tails (donated)
    cfg: LatentMoeConfig,
):
    """ONE fused decode step for a batch of paged sessions: the latent
    family's twin of ``kv_paging.paged_decode_batch_step_jit`` (same block
    table, same masking of padded context and empty tail slots, same
    per-row tail insertion, a row with ``tail_len`` 0 reading its tail as
    zeros). Rows at and past ``n_real`` are padding: they
    are routed to no expert and counted nowhere. Returns (logits (B, V)
    float32, new tail, () int32 distinct (layer, expert) pairs that
    received a real token)."""
    pos, tail_len, ctx_len = meta[:, 0], meta[:, 1], meta[:, 2]
    L, B, _, P, W = tail.shape
    C = table.shape[1] * P
    dt = jnp.dtype(cfg.dtype)
    real = jnp.arange(B) < n_real
    # (B, MP) rows -> (L, B, C, W). Padded table slots gather pool row 0;
    # ctx_len masks them.
    ctx = jnp.take(pool, table, axis=0)[:, :, :, 0].transpose(
        2, 0, 1, 3, 4).reshape(L, B, C, W)
    valid = jnp.concatenate(
        [jnp.arange(C)[None, :] < ctx_len[:, None],
         jnp.arange(P)[None, :] <= tail_len[:, None]], axis=1)
    slot = (jnp.arange(P)[None, :] == tail_len[:, None])[:, :, None]
    # A row that enters with tail_len 0 reads its tail as zeros, as in the
    # dense step: the engine leaves a shipped page in its seat.
    live = (tail_len > 0)[:, None, None]
    X = _embed_streams(params, tokens, cfg)
    touched = jnp.int32(0)
    for i in range(L):
        state = {}

        def attention(h, i=i, state=state):
            with jax.named_scope("mla"):
                qn, qr, entry = latent_qkv(h, params, i, pos, cfg)
                t = jnp.where(slot, entry[:, None, :].astype(tail.dtype),
                              jnp.where(live, tail[i, :, 0], 0))
                state["tail"] = t
                latent = jnp.concatenate([ctx[i].astype(dt), t.astype(dt)],
                                         axis=1)
                return attend_absorbed(qn, qr, latent, valid, params, i,
                                       cfg), None

        X, _ = _sublayer(X, params, i, 0, cfg, attention)
        tail = tail.at[i, :, 0].set(state["tail"])
        X, (n_hit, _) = _sublayer(
            X, params, i, 1, cfg, lambda h, i=i: _ffn(h, params, i, real, cfg))
        touched = touched + n_hit
    return _logits(params, X, cfg), tail, touched


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4,))
def latent_decode_page_jit(
    params: dict,
    tokens_page: jax.Array,  # (1, P) one full page of token ids
    meta: jax.Array,         # (2,) int32 [pos0, -]
    ctx: jax.Array,          # (L, 1, 1, C, W) paged context; C may be 0
    tail: jax.Array,         # (L, 1, 1, P, W) tail buffer (donated)
    cfg: LatentMoeConfig,
):
    """One full page of prefill as ONE program that takes the page's P
    tokens through each layer TOGETHER (every weight read once a page):
    expanded attention over the latent context and, causally, the page's
    own entries. Starts from an empty tail. Returns (logits (1, P, V), the
    full tail, () int32 distinct (layer, expert) pairs touched)."""
    L, _, _, P, W = tail.shape
    C = ctx.shape[3]
    dt = jnp.dtype(cfg.dtype)
    positions = meta[0] + jnp.arange(P)
    mask = jnp.concatenate(
        [jnp.ones((P, C), bool), jnp.tril(jnp.ones((P, P), bool))], axis=1)
    real = jnp.ones((P,), bool)
    X = _embed_streams(params, tokens_page[0], cfg)
    touched = jnp.int32(0)
    for i in range(L):
        state = {}

        def attention(h, i=i, state=state):
            with jax.named_scope("mla"):
                qn, qr, entry = latent_qkv(h, params, i, positions, cfg)
                state["tail"] = entry.astype(tail.dtype)
                latent = jnp.concatenate(
                    [ctx[i, 0, 0].astype(dt), entry.astype(dt)], axis=0)
                return attend_expanded(qn, qr, latent, mask, params, i,
                                       cfg), None

        X, _ = _sublayer(X, params, i, 0, cfg, attention)
        tail = tail.at[i, 0, 0].set(state["tail"])
        X, (n_hit, _) = _sublayer(
            X, params, i, 1, cfg, lambda h, i=i: _ffn(h, params, i, real, cfg))
        touched = touched + n_hit
    return _logits(params, X, cfg)[None], tail, touched


@partial(jax.jit, donate_argnums=(0,))
def latent_pool_write_row_jit(pool: jax.Array, page: jax.Array,
                              slot: jax.Array):
    """Write one page (L, 1, 1, P, W) into row ``slot`` of the pool
    (N, L, 1, P, W), in place (the one-leaf twin of
    ``kv_paging.paged_pool_write_row_jit``)."""
    return jax.lax.dynamic_update_slice(pool, page[None, :, 0],
                                        (slot, 0, 0, 0, 0))


def _leaf_dims(cfg: LatentMoeConfig) -> tuple:
    return (1, cfg.latent_width)


def _step(params, tokens, meta, n_real, pool, table, tails, cfg):
    logits, tail, touched = latent_decode_batch_step_jit(
        params, tokens, meta, np.int32(n_real), pool[0], table, tails[0], cfg)
    return logits, (tail,), touched


def _page(params, tokens_page, meta, ctx, tails, cfg):
    logits, tail, touched = latent_decode_page_jit(
        params, tokens_page, meta, ctx[0], tails[0], cfg)
    return logits, (tail,), touched


def _write_row(pool, page, slot):
    return (latent_pool_write_row_jit(pool[0], page[0], slot),)


def _assignments_per_token(cfg: LatentMoeConfig) -> int:
    return cfg.num_experts_per_tok * cfg.n_expert_layers


PAGED_FAMILY = PagedFamily(
    n_leaves=1, leaf_dims=_leaf_dims, step=_step, page=_page,
    write_row=_write_row, assignments_per_token=_assignments_per_token,
)
