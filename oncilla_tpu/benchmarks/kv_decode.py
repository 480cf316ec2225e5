"""Decode throughput with OCM-paged KV cache — BASELINE.md config 5.

Measures single-chip tokens/s for a Llama-style decoder in four modes:

- ``fused``: the whole decode as ONE compiled program
  (``llama.decode_loop`` — lax.scan with a donated in-place cache). The
  true ceiling: one host dispatch for the entire sequence.
- ``plain``: per-token ``llama.decode_step`` calls with a donated in-HBM
  cache — the dispatch-per-token reference loop. Where this is
  dispatch-latency-bound, modes with smaller per-step buffers
  (the paged arms) can legitimately exceed it; overhead is therefore
  reported against ``fused``, not ``plain``.
- ``device``: KV history paged through OCM into the chip's HBM *arena*
  (``OcmKind.LOCAL_DEVICE``) via :class:`BucketedPagedDecoder` — on a pod
  the same loop lands pages in a *remote* chip's arena over ICI.
- ``host``: pages ride to host DRAM (``OcmKind.LOCAL_HOST``) — the
  device->host->device round trip is the single-chip analogue of the DCN
  arm.
- ``device_fused``: OCM-paged like ``device`` but ONE dispatch per page
  (``BucketedPagedDecoder.step_page``: the page's teacher-forced tokens
  go through each layer together, the weights read once a page), the
  prefill shape of the serving loop, with the data plane on the path. It
  is no decode loop any more and may exceed ``fused``.

The bucketed decoder keeps shapes static per page (O(tokens/page)
compilations), which is what makes this measurable on real hardware: the
unjitted reference path recompiles every token.

The paged arms run the decoder with ``refetch=True``: every completed page
is shipped out with a one-sided put AND the whole paged context is read
back through one-sided gets at each page boundary, so both directions of
the data plane are on the measured path (the usage pattern of
/root/reference/test/ocm_test.c test 2, with a transformer as the
application; the reference has no ML analogue).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.benchmarks._util import fence as _sync
from oncilla_tpu.core.kinds import OcmKind
from oncilla_tpu.models import llama
from oncilla_tpu.models.kv_paging import BucketedPagedDecoder


_decode_step = partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(3,)
)(llama.decode_step)
_decode_loop = partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(2,)
)(llama.decode_loop)


def _run_cfg(cfg, tokens):
    """Cache sized to the decoded length, not cfg.max_seq, so per-step
    attention work matches the paged arms (a 2048-slot cache for a
    384-token run would understate the reported paging overhead)."""
    import dataclasses

    return dataclasses.replace(cfg, max_seq=tokens.shape[1])


def bench_plain(params, cfg, tokens) -> float:
    """Tokens/s for the dispatch-per-token in-HBM decode loop (donated
    cache, one jit call per token)."""
    cfg = _run_cfg(cfg, tokens)

    def run():
        kv = llama.make_kv_cache(cfg, 1, dtype=cfg.dtype)
        logits = None
        for i in range(tokens.shape[1]):
            logits, kv = _decode_step(
                params, tokens[:, i], jnp.int32(i), kv, cfg
            )
        _sync(logits)

    run()  # compile
    run()  # re-warm: donated outputs settle into steady-state layouts
    t0 = time.perf_counter()
    run()
    return tokens.shape[1] / (time.perf_counter() - t0)


def bench_fused(params, cfg, tokens) -> float:
    """Tokens/s for the whole-sequence scan decode — the single-dispatch
    ceiling every other mode is compared against."""
    cfg = _run_cfg(cfg, tokens)

    def run():
        kv = llama.make_kv_cache(cfg, 1, dtype=cfg.dtype)
        logits, _ = _decode_loop(params, tokens, kv, cfg)
        _sync(logits)

    run()  # compile
    run()  # re-warm (donation layouts)
    t0 = time.perf_counter()
    run()
    return tokens.shape[1] / (time.perf_counter() - t0)


def bench_paged(params, cfg, tokens, ctx, kind, page_tokens) -> float:
    """Tokens/s with KV history paged through OCM handles."""

    def run():
        dec = BucketedPagedDecoder(
            params, cfg, ctx, batch=1, page_tokens=page_tokens, kind=kind,
            dtype=cfg.dtype, refetch=True,
        )
        logits = None
        for i in range(tokens.shape[1]):
            logits = dec.step(tokens[:, i])
        _sync(logits)
        dec.close()

    run()  # compile all page buckets
    t0 = time.perf_counter()
    run()
    return tokens.shape[1] / (time.perf_counter() - t0)


def bench_paged_fused(params, cfg, tokens, ctx, kind, page_tokens) -> float:
    """Tokens/s with OCM-paged KV and ONE dispatch per page
    (BucketedPagedDecoder.step_page): a page's tokens go through each
    layer together on-chip, page put/get through the data plane between
    dispatches (still refetch=True, so both directions are measured)."""
    n_pages = tokens.shape[1] // page_tokens

    def run():
        dec = BucketedPagedDecoder(
            params, cfg, ctx, batch=1, page_tokens=page_tokens, kind=kind,
            dtype=cfg.dtype, refetch=True,
        )
        logits = None
        for p in range(n_pages):
            logits = dec.step_page(
                tokens[:, p * page_tokens:(p + 1) * page_tokens]
            )
        _sync(logits)
        dec.close()

    run()  # compile all page buckets
    t0 = time.perf_counter()
    run()
    return n_pages * page_tokens / (time.perf_counter() - t0)


def run_bench(
    tokens_n: int = 384,
    page_tokens: int = 128,
    # Scan-heavy modes run LAST: donating buffers through a big scan
    # executable leaves the chip in a state where subsequent per-step
    # dispatch loses 2-3x throughput (same stickiness bench.py documents
    # for the DMA loops) — measured: plain reads 196 tok/s before fused,
    # 73 after. device_fused (one program per page) sits just before fused.
    modes: tuple = ("plain", "device", "host", "device_fused", "fused"),
    config: str = "small",
) -> dict:
    """Programmatic entry (bench.py and the CLI share it): tokens/s per
    mode plus the paging overhead vs the in-HBM ceiling."""
    import oncilla_tpu as ocm

    cfg = llama.LlamaConfig() if config == "small" else llama.LlamaConfig.tiny()
    params = llama.init_params_host(0, cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab, size=(1, tokens_n), dtype=np.int32)
    )

    # Arena sized for all pages of the run (both timed + warmup sessions
    # free their pages on close).
    page_bytes = (
        2 * cfg.n_layers * cfg.n_kv_heads * page_tokens * cfg.head_dim
        * jnp.dtype(cfg.dtype).itemsize
    )
    npages = tokens_n // page_tokens
    arena = max(64 << 20, 2 * npages * page_bytes)
    ctx = ocm.ocm_init(
        ocm.OcmConfig(host_arena_bytes=arena, device_arena_bytes=arena)
    )

    out = {"config": config, "tokens": tokens_n,
           "page_tokens": page_tokens, "tok_s": {}}
    try:
        _run_modes(out, modes, params, cfg, tokens, ctx, page_tokens)
    finally:
        ocm.ocm_tini(ctx)  # never leak the arenas into the caller's process
    return out


def _run_modes(out, modes, params, cfg, tokens, ctx, page_tokens):
    for mode in modes:
        if mode == "fused":
            tps = bench_fused(params, cfg, tokens)
        elif mode == "plain":
            tps = bench_plain(params, cfg, tokens)
        elif mode == "device":
            tps = bench_paged(
                params, cfg, tokens, ctx, OcmKind.LOCAL_DEVICE, page_tokens
            )
        elif mode == "host":
            tps = bench_paged(
                params, cfg, tokens, ctx, OcmKind.LOCAL_HOST, page_tokens
            )
        elif mode == "device_fused":
            tps = bench_paged_fused(
                params, cfg, tokens, ctx, OcmKind.LOCAL_DEVICE, page_tokens
            )
        else:
            raise ValueError(f"unknown mode {mode!r}")
        out["tok_s"][mode] = round(tps, 2)

    # Paging overhead of the PAGED arms only, vs the single-dispatch
    # ceiling (falling back to the per-step loop when fused wasn't
    # requested). plain's gap vs fused is dispatch latency, not paging —
    # it stays out of this dict.
    base_mode = "fused" if "fused" in out["tok_s"] else "plain"
    if base_mode in out["tok_s"]:
        base = out["tok_s"][base_mode]
        out["overhead_vs"] = base_mode
        out["paging_overhead"] = {
            m: round(base / v - 1.0, 4)
            for m, v in out["tok_s"].items()
            if m in ("device", "host", "device_fused") and v
        }


def main() -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tokens", type=int, default=384)
    ap.add_argument("--page-tokens", type=int, default=128)
    ap.add_argument(
        "--modes", default="plain,device,host,device_fused,fused",
        help="comma list of plain|device|host|device_fused|fused (scan "
             "modes last: see run_bench on measurement-order sensitivity)",
    )
    ap.add_argument("--config", choices=["small", "tiny"], default="small")
    args = ap.parse_args()
    try:
        out = run_bench(
            tokens_n=args.tokens,
            page_tokens=args.page_tokens,
            modes=tuple(m.strip() for m in args.modes.split(",") if m.strip()),
            config=args.config,
        )
    except ValueError as e:
        raise SystemExit(str(e)) from e
    print(json.dumps(out))


if __name__ == "__main__":
    main()
