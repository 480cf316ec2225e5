"""Compile, before the window, the programs of the dense paged path whose
shapes the traffic can reach: one prefill program per context length, one
fused step per (batch, pages, pool rows) bucket, and the small eager
programs (context concatenation, pool stacking) that go with them.

The shapes are data: ``warm.prefill_context_pages`` and
``warm.fused_buckets`` in the traffic file, found by a census of the
schedule (``README.md``). The calls mirror ``ServingEngine._prefill_chunk``
and ``_batch_step`` argument for argument; a program whose entry point has
gone is skipped, and what the warm-up requests then compile, or what
compiles inside the window, shows in ``entry.window_compiles``.
"""

from __future__ import annotations


def warm(engine, cfg, params, spec: dict) -> None:
    import jax
    import jax.numpy as jnp

    try:
        from oncilla_tpu.models import (
            paged_decode_batch_step_jit,
            paged_decode_page_jit,
        )
    except ImportError:
        return
    P = engine.page_tokens
    dt = jnp.dtype(cfg.dtype)
    L, KV, Hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim

    def tail(b: int):
        return jnp.zeros((L, b, KV, P, Hd), dt)

    page = jnp.zeros((L, 1, KV, P, Hd), dt)
    out = None
    for pages in range(int(spec.get("prefill_context_pages", 0))):
        if pages:
            k_ctx = jnp.concatenate([page] * pages, axis=3)
        else:
            k_ctx = jnp.zeros((L, 1, KV, 0, Hd), dt)
        out = paged_decode_page_jit(
            params, jnp.zeros((1, P), jnp.int32),
            jnp.asarray([pages * P, 0], jnp.int32),
            k_ctx, k_ctx, tail(1), tail(1), cfg,
        )
    row = jnp.zeros((L, KV, P, Hd), dt)
    for b, mp, n in spec.get("fused_buckets", []):
        pool = jnp.stack([row] * n)
        out = paged_decode_batch_step_jit(
            params, jnp.zeros((b,), jnp.int32), jnp.zeros((b, 4), jnp.int32),
            pool, pool, jnp.zeros((b, mp), jnp.int32), tail(b), tail(b), cfg,
        )
    if out is not None:
        jax.block_until_ready(out)
