"""Flagship model: a Llama-style decoder-only transformer, pure JAX.

TPU-first design notes:
- All matmuls are einsums over (dim, heads*head_dim)-shaped weights so GSPMD
  can shard heads/ffn over the ``tp`` mesh axis and batch over ``dp``.
- Attention optionally runs as ring attention over a ``sp`` sequence axis
  (:mod:`oncilla_tpu.parallel.ring_attention`) for long-context training.
  K/V stay unexpanded (GQA) all the way into the attention kernels, so the
  ring rotates group-size-times fewer bytes over ICI.
- bfloat16 activations by default (MXU-native); scores/softmax accumulate
  in fp32 on every path.
- Decode uses a KV cache that can be paged into OCM arenas — local or
  *remote* chips' HBM — via :mod:`oncilla_tpu.models.kv_paging`
  (BASELINE.md config 5).

This is demo/benchmark cargo for the disaggregated-memory runtime (the
reference is not an ML framework — SURVEY.md §0); it exists to exercise the
OCM data planes with a real workload.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_hidden: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Sliding-window attention (Mistral scheme): each token attends to at
    # most its last `window` positions. None = full causal attention.
    window: int | None = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def tiny() -> "LlamaConfig":
        """CI-size config for the virtual CPU mesh."""
        return LlamaConfig(
            vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_hidden=128, max_seq=128, dtype="float32",
        )

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        """Llama-3-8B geometry (BASELINE.md config 5)."""
        return LlamaConfig(
            vocab=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_hidden=14336, max_seq=8192, rope_theta=500000.0,
        )

    @staticmethod
    def mistral_7b() -> "LlamaConfig":
        """Mistral-7B v0.1 geometry — the sliding-window flagship shape
        (v0.2 dropped the window and raised rope_theta)."""
        return LlamaConfig(
            vocab=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            ffn_hidden=14336, max_seq=8192, rope_theta=10000.0, window=4096,
        )


LAYER_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln_attn", "ln_mlp"
)


def param_spec(cfg: LlamaConfig) -> dict:
    """{name: (shape, init_scale | None)} for every weight leaf; None means
    a ones-initialized norm gain. The single source of truth both
    initializers consume, so they cannot drift structurally."""
    L, D, H, KV, Hd, F = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
        cfg.ffn_hidden,
    )
    s_in = 1.0 / np.sqrt(D)
    s_out = 1.0 / np.sqrt(2 * L * D)
    return {
        "embed": ((cfg.vocab, D), 1.0),
        "wq": ((L, D, H * Hd), s_in),
        "wk": ((L, D, KV * Hd), s_in),
        "wv": ((L, D, KV * Hd), s_in),
        "wo": ((L, H * Hd, D), s_out),
        "w_gate": ((L, D, F), s_in),
        "w_up": ((L, D, F), s_in),
        "w_down": ((L, F, D), s_out),
        "ln_attn": ((L, D), None),
        "ln_mlp": ((L, D), None),
        "ln_out": ((D,), None),
        "lm_head": ((D, cfg.vocab), s_in),
    }


def init_from_spec(key: jax.Array, spec: dict, dtype) -> dict:
    """Scaled-normal init of a {name: (shape, scale|None)} spec; None means
    a ones-initialized norm gain. Shared by the dense and MoE families."""
    dt = jnp.dtype(dtype)
    keys = jax.random.split(key, len(spec))
    out = {}
    for k, (name, (shape, scale)) in zip(keys, spec.items()):
        if scale is None:
            out[name] = jnp.ones(shape, dtype=jnp.float32)
        else:
            out[name] = (
                jax.random.normal(k, shape, dtype=jnp.float32) * scale
            ).astype(dt)
    return out


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Scaled-normal init; layers stacked along a leading axis so the whole
    model is a handful of leaves (sharding-friendly)."""
    return init_from_spec(key, param_spec(cfg), cfg.dtype)


def init_params_host(seed: int, cfg: LlamaConfig) -> dict:
    """Same pytree as :func:`init_params` (not bit-identical), built with
    numpy on the host and transferred. The jax.random path compiles one
    kernel per weight shape before the first real compile starts;
    benchmarks that do not care about the exact init use this."""
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(cfg.dtype)
    out = {}
    for name, (shape, scale) in param_spec(cfg).items():
        if scale is None:
            out[name] = jax.device_put(np.ones(shape, dtype=np.float32))
        else:
            x = rng.standard_normal(shape, dtype=np.float32) * scale
            out[name] = jax.device_put(x.astype(dt))
    return out


def layer_params(params: dict, i: int) -> dict:
    return {k: params[k][i] for k in LAYER_KEYS}


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, H, S, Hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, hd/2)
        ang = ang[None, None]
    else:
        ang = positions[:, None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def grouped_attention(q, k, v, mask=None):
    """Dense attention with unexpanded GQA K/V, fp32 softmax.

    q: (B, H, Sq, D); k/v: (B, KV, Sk, D) with KV dividing H;
    mask: (Sq, Sk) bool, (B, Sq, Sk) bool (per-sequence validity — the
    batched-serving path, where each row carries its own padded-context
    mask), or None. Returns (B, H, Sq, D) in q's dtype."""
    B, H, Sq, D = q.shape
    KV = k.shape[1]
    q5 = q.reshape(B, KV, H // KV, Sq, D)
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum(
        "bkgqd,bksd->bkgqs", q5, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        m = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bkgqs,bksd->bkgqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, H, Sq, D).astype(q.dtype)


def causal_mask(sq: int, sk: int, window: int | None = None) -> jax.Array:
    """Lower-triangular mask aligned to the *end* of the key axis (the self-
    attention case where the last sq keys are the queries' own positions).
    With ``window``, additionally band-limits each query to its last
    ``window`` keys (sliding-window attention, the Mistral long-context
    scheme): key j attends to query i iff i-window < j-(sk-sq) ≤ i."""
    m = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
    if window is not None:
        m &= jnp.triu(jnp.ones((sq, sk), dtype=bool), k=sk - sq - window + 1)
    return m


def block(cfg: LlamaConfig, x, lp, positions, attend, mlp=None):
    """One transformer block — the single implementation every path uses.

    x: (B, S, D); lp: this layer's params; ``attend(q, kn, vn)`` receives
    this block's fresh rotary-embedded q (B, H, S, Hd) and *unexpanded* KV
    (B, KV, S, Hd) and returns the attention output (B, H, S, Hd) — the
    callback decides dense/ring/cached attention. ``mlp(h)`` (if given)
    replaces the dense SwiGLU FFN on the rmsnorm'd residual — the hook the
    MoE family (:mod:`oncilla_tpu.models.moe`) plugs its expert layer into.
    """
    B, S, D = x.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h = rmsnorm(x, lp["ln_attn"], cfg.norm_eps)
    q = jnp.einsum("bsd,dh->bsh", h, lp["wq"]).reshape(B, S, H, Hd)
    kn = jnp.einsum("bsd,dh->bsh", h, lp["wk"]).reshape(B, S, KV, Hd)
    vn = jnp.einsum("bsd,dh->bsh", h, lp["wv"]).reshape(B, S, KV, Hd)
    q = rope(q.transpose(0, 2, 1, 3), positions, cfg.rope_theta)
    kn = rope(kn.transpose(0, 2, 1, 3), positions, cfg.rope_theta)
    vn = vn.transpose(0, 2, 1, 3)
    attn = attend(q, kn, vn)  # (B, H, S, Hd)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, S, H * Hd)
    x = x + jnp.einsum("bsh,hd->bsd", attn, lp["wo"])

    h = rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
    if mlp is not None:
        return x + mlp(h)
    gate = jnp.einsum("bsd,df->bsf", h, lp["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["w_down"])


def final_logits(params, x, cfg: LlamaConfig) -> jax.Array:
    x = rmsnorm(x, params["ln_out"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def make_attend(S: int, mesh=None, seq_axis: str | None = None,
                window: int | None = None):
    """The dense-vs-ring attention dispatch shared by every model family:
    with ``mesh`` + ``seq_axis`` the callback runs ring attention over the
    sequence-sharded axis, else causal dense attention over S keys.
    ``window`` band-limits either path (sliding-window attention; the ring
    applies it from global positions inside each ring step)."""
    if seq_axis is not None:
        from oncilla_tpu.parallel.ring_attention import ring_attention

        def attend(q, kn, vn):
            return ring_attention(
                q, kn, vn, mesh, axis_name=seq_axis, causal=True,
                window=window,
            )
    else:
        def attend(q, kn, vn):
            return grouped_attention(q, kn, vn, causal_mask(S, S, window))

    return attend


def _remat_wrap(fn, remat):
    """``remat`` placement options (the r3 "remat placement sweep"):
    False = store all block activations; True = full per-block checkpoint
    (recompute everything in backward — max memory saving, ~1 extra
    forward of matmul work); "dots" = checkpoint with the dots-saveable
    policy (matmul outputs are kept, only elementwise/softmax intermediates
    recompute — most of the memory saving at ~zero extra MXU work)."""
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if remat:
        return jax.checkpoint(fn)
    return fn


def forward_hidden(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    mesh=None,
    seq_axis: str | None = None,
    remat=False,
) -> jax.Array:
    """Final hidden states (B, S, D), pre-``ln_out``. With ``mesh`` +
    ``seq_axis``, attention runs as ring attention over the
    sequence-sharded axis; ``remat`` per :func:`_remat_wrap`."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    positions = jnp.arange(S)
    attend = make_attend(S, mesh, seq_axis, window=cfg.window)

    def one_block(x, lp):
        return block(cfg, x, lp, positions, attend)

    one_block = _remat_wrap(one_block, remat)
    for i in range(cfg.n_layers):
        x = one_block(x, layer_params(params, i))
    return x


def forward(params: dict, tokens: jax.Array, cfg: LlamaConfig, **kw) -> jax.Array:
    """Logits for a token batch (B, S) (see :func:`forward_hidden`)."""
    return final_logits(params, forward_hidden(params, tokens, cfg, **kw), cfg)


def blocked_cross_entropy(
    params: dict, x: jax.Array, targets: jax.Array, cfg: LlamaConfig,
    block: int = 512,
) -> jax.Array:
    """Next-token CE without materializing the (B, S, V) logits: the vocab
    head runs per sequence chunk inside a rematerialized scan, so peak
    memory is O(B·block·V) and the backward recomputes each chunk's logits
    instead of storing S·V floats of log-softmax. ``x`` is the pre-``ln_out`` hidden (B, S, D);
    ``targets`` is (B, S-1)."""
    xh = rmsnorm(x, params["ln_out"], cfg.norm_eps)[:, :-1]
    B, T, D = xh.shape
    pad = (-T) % block
    mask = jnp.arange(T + pad)[None, :] < T          # (1, T+pad)
    mask = jnp.broadcast_to(mask, (B, T + pad))
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    n = (T + pad) // block
    xh = xh.reshape(B, n, block, D).transpose(1, 0, 2, 3)
    tg = targets.reshape(B, n, block).transpose(1, 0, 2)
    mk = mask.reshape(B, n, block).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll(xc, tc, mc):
        logits = jnp.einsum(
            "bsd,dv->bsv", xc, params["lm_head"]
        ).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * mc)

    def body(acc, args):
        return acc + chunk_nll(*args), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xh, tg, mk))
    return total / (B * T)


def loss_fn(params, tokens, cfg: LlamaConfig, *, ce_block: int | None = None,
            **kw) -> jax.Array:
    """Next-token cross entropy. ``ce_block`` switches to the blocked/
    rematerialized vocab-head CE (:func:`blocked_cross_entropy`)."""
    if ce_block is not None:
        x = forward_hidden(params, tokens, cfg, **kw)
        return blocked_cross_entropy(x=x, params=params,
                                     targets=tokens[:, 1:], cfg=cfg,
                                     block=ce_block)
    logits = forward(params, tokens, cfg, **kw)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# -- decode-time attention over a KV cache --------------------------------


def decode_step(
    params: dict,
    token: jax.Array,         # (B,) current token ids
    pos: jax.Array,           # scalar current position
    kv_cache: tuple,          # (k, v) each (L, B, KV, max_seq, Hd)
    cfg: LlamaConfig,
    *,
    layer_params_fn=layer_params,
    mlp_of=None,
):
    """Single-token decode: returns (logits, new_kv_cache). The cache layout
    is the one :mod:`oncilla_tpu.models.kv_paging` pages through OCM.

    ``layer_params_fn`` / ``mlp_of`` are the family hooks: the MoE family
    passes its layer-slicer and an ``mlp_of(lp) -> mlp`` factory so the
    same cache machinery decodes a sparse-FFN model
    (:func:`oncilla_tpu.models.moe.decode_step`)."""
    x = params["embed"][token][:, None, :].astype(jnp.dtype(cfg.dtype))  # (B,1,D)
    k_cache, v_cache = kv_cache
    positions = pos[None] if pos.ndim == 0 else pos
    T = k_cache.shape[3]
    valid = (jnp.arange(T)[None, :] <= pos)  # (1, T)
    if cfg.window is not None:
        valid &= jnp.arange(T)[None, :] > pos - cfg.window

    for i in range(cfg.n_layers):
        lp = layer_params_fn(params, i)
        state = {}

        def attend(q, kn, vn, i=i, state=state):
            kc = jax.lax.dynamic_update_slice(
                k_cache[i], kn.astype(k_cache.dtype), (0, 0, pos, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                v_cache[i], vn.astype(v_cache.dtype), (0, 0, pos, 0)
            )
            state["kc"], state["vc"] = kc, vc
            return grouped_attention(
                q, kc.astype(q.dtype), vc.astype(q.dtype), valid
            )

        x = block(cfg, x, lp, positions, attend,
                  mlp=mlp_of(lp) if mlp_of else None)
        k_cache = k_cache.at[i].set(state["kc"])
        v_cache = v_cache.at[i].set(state["vc"])

    logits = final_logits(params, x, cfg)
    return logits[:, 0], (k_cache, v_cache)


def make_kv_cache(cfg: LlamaConfig, batch: int, dtype=None):
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.max_seq, cfg.head_dim)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def decode_loop(params, tokens: jax.Array, kv_cache: tuple, cfg: LlamaConfig,
                *, step_fn=None):
    """Whole-sequence decode as ONE compiled program: ``lax.scan`` over the
    token positions with the KV cache threaded (and donated) through the
    carry — the static-control-flow formulation XLA wants, and the true
    single-chip decode ceiling (the per-step :func:`decode_step` loop pays
    one host dispatch per token; this pays one per sequence).

    tokens: (B, N) teacher-forced ids, N ≤ cfg.max_seq. Returns
    (logits (B, N, vocab), final kv_cache). jit with
    ``static_argnames=("cfg",)`` and ``donate_argnums=(2,)``. ``step_fn``
    swaps in another family's decode step (e.g. the MoE one).
    """
    step_fn = step_fn or decode_step

    def body(carry, tok):
        kv, pos = carry
        logits, kv = step_fn(params, tok, pos, kv, cfg)
        return (kv, pos + 1), logits

    (kv_cache, _), logits = jax.lax.scan(
        body, (kv_cache, jnp.int32(0)), tokens.T
    )
    return logits.transpose(1, 0, 2), kv_cache


def sample_token(logits_b: jax.Array, key: jax.Array, temperature: float,
                 dtype) -> jax.Array:
    """Greedy at ``temperature`` 0, else softmax sampling — THE sampler,
    shared by :func:`generate` and the paged serving loop
    (``kv_paging.paged_generate_page_jit``) so the two cannot diverge.
    ``temperature`` must be trace-static (the greedy branch is Python-level)."""
    if temperature == 0.0:
        return jnp.argmax(logits_b, axis=-1).astype(dtype)
    return jax.random.categorical(
        key, logits_b / jnp.float32(temperature), axis=-1
    ).astype(dtype)


def generate(
    params,
    prompt: jax.Array,
    kv_cache: tuple,
    cfg: LlamaConfig,
    steps: int,
    *,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    step_fn=None,
):
    """Autoregressive continuation as ONE compiled program: teacher-forced
    prefill over the prompt (scan), then ``steps`` sampled tokens (scan),
    greedy when ``temperature`` == 0 else softmax sampling with ``key``.
    ``step_fn`` swaps in another family's decode step (e.g. the MoE one).

    prompt: (B, P) ids; P + steps ≤ cfg.max_seq. Returns ((B, steps)
    sampled ids, final kv_cache) — the cache covers every *consumed*
    token (prompt + the first steps-1 samples; the final sample is
    output-only), so a caller can keep decoding from position
    P + steps - 1, and the recommended jit config
    ``static_argnames=("cfg", "steps", "temperature")`` +
    ``donate_argnums=(2,)`` can reuse the donated cache buffers for the
    output.
    """
    B, P = prompt.shape
    step_fn = step_fn or decode_step
    logits, kv_cache = decode_loop(params, prompt, kv_cache, cfg,
                                   step_fn=step_fn)

    if key is None:
        key = jax.random.key(0)

    def pick(logits_b, k):
        return sample_token(logits_b, k, temperature, prompt.dtype)

    first = pick(logits[:, -1], key)

    def body(carry, k_i):
        kv, pos, tok = carry
        step_logits, kv = step_fn(params, tok, pos, kv, cfg)
        nxt = pick(step_logits, k_i)
        return (kv, pos + 1, nxt), tok

    # first is sample 1; the scan produces the remaining steps-1, each tick
    # feeding the previous sample and emitting it into `out`.
    keys = jax.random.split(jax.random.fold_in(key, 1), steps - 1)
    (kv_cache, _, last), out = jax.lax.scan(
        body, (kv_cache, jnp.int32(P), first), keys
    )
    seq = jnp.concatenate([out, last[None]], axis=0)  # (steps, B)
    return seq.transpose(1, 0), kv_cache
