"""Model FLOPs Utilization for the flagship model on one chip.

The judged single-chip compute metric: achieved matmul FLOP/s on the
flagship decoder divided by the chip's peak (bf16). The reference has no
analogue (it is a memory framework, SURVEY.md §0); the measurement shape
follows its benchmark idiom — N timed iterations of the hot loop after a
warm-up, excluded setup (test/ib_client.c:24 "excluded from timing").

FLOPs are counted analytically per matmul (2·m·n·k), not estimated with the
6·N·D rule, so GQA and the LM head are exact.
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

from oncilla_tpu.models.llama import LlamaConfig

# Peak dense bf16 FLOP/s per chip. v5e: 197 TFLOP/s (could be overridden
# for other generations via OCM_PEAK_TFLOPS).
PEAK_TFLOPS = float(os.environ.get("OCM_PEAK_TFLOPS", 197.0))


def forward_flops(cfg: LlamaConfig, batch: int, seq: int) -> int:
    """Exact matmul FLOPs of one forward pass (2mnk per matmul; elementwise
    and norms excluded — they are noise against the matmuls)."""
    b, s, d = batch, seq, cfg.dim
    hd = cfg.head_dim
    kv_dim = cfg.n_kv_heads * hd
    per_layer = (
        2 * b * s * d * d                 # Wq
        + 2 * 2 * b * s * d * kv_dim      # Wk, Wv
        + 2 * b * s * d * d               # Wo
        + 2 * 2 * b * cfg.n_heads * s * s * hd  # QK^T and PV
        + 3 * 2 * b * s * d * cfg.ffn_hidden    # gate, up, down
    )
    head = 2 * b * s * d * cfg.vocab
    return cfg.n_layers * per_layer + head


def train_flops(cfg: LlamaConfig, batch: int, seq: int) -> int:
    """Backward re-does ~2x the forward matmul work (grad wrt inputs and
    weights), so a train step is ~3x forward."""
    return 3 * forward_flops(cfg, batch, seq)


def chip_filling_config() -> tuple[LlamaConfig, int, int]:
    """~1.1B-param bf16 decoder + (batch, seq) sized for one v5e chip
    (16 GB HBM): ~2.3 GB of weights, long enough matmuls to saturate the
    MXU."""
    cfg = LlamaConfig(
        vocab=32000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        ffn_hidden=8192, max_seq=2048, dtype="bfloat16",
    )
    return cfg, 8, 1024


def train_sized_config() -> tuple[LlamaConfig, int, int]:
    """The same ~1.1B flagship geometry as the forward measurement, batch
    sized down so params + grads + Adam moments (~4 weight copies) fit
    alongside activations. Measured on v5e: batch 4 gives 0.56 MFU; batch
    8 fails to compile (out of HBM), and a smaller ~0.4B model at batch 8
    reads lower (0.535) — bigger matmuls beat a bigger batch."""
    cfg, _, _ = chip_filling_config()
    return cfg, 4, 1024


def _sync(x) -> None:
    jax.block_until_ready(x)


def mfu_forward(
    cfg: LlamaConfig | None = None,
    batch: int | None = None,
    seq: int | None = None,
    steps: int = 10,
) -> dict:
    """Forward-pass MFU on the default device."""
    from oncilla_tpu.models import llama

    if cfg is None:
        cfg, batch, seq = chip_filling_config()
    # Host-side init: the jax.random path compiles one kernel per weight
    # shape and the exact init values are irrelevant to a FLOP/s
    # measurement.
    params = llama.init_params_host(0, cfg)
    tokens = jax.device_put(
        np.random.default_rng(0).integers(0, cfg.vocab, (batch, seq),
                                          dtype=np.int32)
    )

    @jax.jit
    def fwd(p, t):
        return llama.forward(p, t, cfg)

    out = fwd(params, tokens)
    _sync(out)  # compile + warm-up excluded from timing
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(params, tokens)
    _sync(out)
    dt = time.perf_counter() - t0
    achieved = forward_flops(cfg, batch, seq) * steps / dt
    return {
        "mfu": achieved / (PEAK_TFLOPS * 1e12),
        "tflops": achieved / 1e12,
        "flops_per_step": forward_flops(cfg, batch, seq),
        "steps": steps,
        "seconds": dt,
    }


def mfu_train(
    cfg: LlamaConfig | None = None,
    batch: int | None = None,
    seq: int | None = None,
    steps: int = 6,
    remat=False,
    ce_block: int | None = None,
    mu_dtype=None,
    fold: bool = False,
) -> dict:
    """Train-step MFU (fwd + bwd + optimizer) on a single-device mesh.

    ``fold=True`` compiles all ``steps`` gradient steps into ONE dispatch
    (train.make_train_step(fold_steps=)) so the timed window contains no
    per-step host round-trips; the unfolded twin quantifies what those
    cost. Both flavors run the identical per-step math on the same fixed
    batch.

    Donation audit: params and opt_state are donated
    through the step (train._jit_step donate_argnums=(0, 1)) with output
    params pinned to the input specs, so XLA updates weights and Adam
    moments in place — no extra weight copies live across the step. The
    remaining knobs are ``remat`` ("dots" keeps matmul outputs, recomputes
    elementwise — batch can grow with ~zero extra MXU work), ``ce_block``
    (blocked vocab-head CE — no (B, S, V) logits tensor) and ``mu_dtype``
    (bf16 Adam µ — halves µ footprint+traffic, frees ~2 GB of HBM on the
    flagship so bigger batches fit WITHOUT paying the blocked-CE tax);
    :func:`mfu_train_best` sweeps them."""
    from oncilla_tpu.models import train

    if cfg is None:
        cfg, batch, seq = train_sized_config()
    mesh = train.make_mesh(1)
    # Host-side init (same rationale as mfu_forward); the optimizer is the
    # production one from train.py, so this measures the real train step.
    params, opt_state, tx = train.make_train_state_host(
        0, cfg, mesh, mu_dtype=mu_dtype
    )
    step = train.make_train_step(cfg, mesh, tx, use_ring=False,
                                 remat=remat, ce_block=ce_block,
                                 fold_steps=steps if fold else 0)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        train.sample_batch(rng, cfg, batch, seq),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )
    # TWO warm-up steps: the first compiles; the first call's donated
    # outputs come back with different buffer layouts than the freshly
    # device_put inputs, so the SECOND call compiles again for the
    # steady-state layouts (measured ~25 s each on v5e — one warm-up step
    # left a full compile inside the timed loop, reading 0.02 MFU for a
    # 0.31-MFU step).
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, tokens)
    _sync(params["wq"])
    t0 = time.perf_counter()
    if fold:
        # One dispatch contains all `steps` gradient steps.
        params, opt_state, loss = step(params, opt_state, tokens)
    else:
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, tokens)
    # Any output of the step executable works as the sync point (all
    # outputs of one jit call become ready together); params reads as the
    # clearer statement that the full update chain is being timed.
    _sync(params["wq"])
    dt = time.perf_counter() - t0
    achieved = train_flops(cfg, batch, seq) * steps / dt
    return {
        "mfu": achieved / (PEAK_TFLOPS * 1e12),
        "tflops": achieved / 1e12,
        "loss": float(loss),
        "steps": steps,
        "seconds": dt,
        "batch": batch,
        "remat": str(remat),
        "ce_block": ce_block,
        "mu_dtype": str(mu_dtype.__name__) if mu_dtype is not None else None,
        "fold": fold,
    }


def train_variants() -> list[dict]:
    """The sweep grid of :func:`mfu_train_best`, expected-value-descending
    (see there for the rationale).
    ce_block never exceeds the effective sequence (seq-1 = 1023, padded
    to the block size): 1024 is one near-exact chunk; a 2048 block would
    pad HALF the chunk with masked positions and materialize MORE logits
    than the unblocked head it exists to avoid."""
    import jax.numpy as jnp

    _, batch4, _ = train_sized_config()
    bf16 = jnp.bfloat16
    return [
        # (the champion hypothesis: no CE-blocking tax, Adam amortized,
        # all timed steps folded into one dispatch so per-dispatch
        # latency is out of the window; the unfolded twin right after
        # quantifies it)
        dict(batch=8, remat="dots", ce_block=None, mu_dtype=bf16, fold=True),
        dict(batch=8, remat="dots", ce_block=None, mu_dtype=bf16),
        dict(batch=16, remat="dots", ce_block=1024, mu_dtype=bf16, fold=True),
        dict(batch=batch4, remat=False, ce_block=None, mu_dtype=bf16, fold=True),
        dict(batch=16, remat="dots", ce_block=1024, mu_dtype=None),
        dict(batch=batch4, remat=False, ce_block=None, mu_dtype=None),  # r3 floor
        dict(batch=8, remat="dots", ce_block=1024, mu_dtype=None),      # r5 floor
        dict(batch=16, remat=True, ce_block=1024, mu_dtype=bf16),
    ]


def variant_label(v: dict) -> dict:
    """JSON-serializable form of a sweep-grid entry (mu_dtype by name,
    fold always present so folded/unfolded twins pair up in the banked
    variants table even on error/skip rows)."""
    return {
        **v,
        "mu_dtype": v["mu_dtype"].__name__ if v["mu_dtype"] else None,
        "fold": v.get("fold", False),
    }


def mfu_train_best(deadline: float | None = None) -> dict:
    """Sweep the memory-layout variants of the train step and keep the
    best MFU. The analytic FLOP count (3x forward) is identical for every
    variant, so wall time alone decides — a variant that recomputes more
    must win on time to win here.

    Variant order encodes what the r5 first-light measurements showed:
    batch 4 with UNBLOCKED CE (r3: 0.554) beats batch 8 with blocked CE
    (r5: 0.525-0.531) — the CE scan's small per-block head matmuls cost
    more MFU than batch-8's Adam amortization buys. So the leading
    hypothesis is batch 8 + dots-remat + *unblocked* CE, which only fits
    in 16 GB because bf16-µ (``mu_dtype``) frees ~2.2 GB of moment
    footprint; then the amortization ladder (batch 16 needs blocked CE
    again — its full logits don't fit at any µ dtype), then the measured
    incumbents as floors. With ``deadline`` (time.monotonic()), later
    variants are skipped once it passes; a variant that fails (e.g. OOM
    at compile) is recorded and skipped."""
    cfg, _, seq = train_sized_config()
    best, tried = None, []
    for v in train_variants():
        label = variant_label(v)
        if deadline is not None and time.monotonic() > deadline:
            tried.append({**label, "skipped": "deadline"})
            continue
        try:
            r = mfu_train(cfg, v["batch"], seq, remat=v["remat"],
                          ce_block=v["ce_block"], mu_dtype=v["mu_dtype"],
                          fold=v.get("fold", False))
        except Exception as e:  # noqa: BLE001 — an OOM variant is data
            tried.append({**label, "error": f"{type(e).__name__}"})
            continue
        tried.append(
            {k: r[k] for k in ("batch", "remat", "ce_block", "mu_dtype", "fold", "mfu")}
        )
        if best is None or r["mfu"] > best["mfu"]:
            best = r
    if best is None:
        raise RuntimeError(f"every mfu_train variant failed: {tried}")
    best["variants"] = tried
    return best
