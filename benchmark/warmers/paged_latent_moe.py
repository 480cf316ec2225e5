"""Compile, before the window, the programs of the latent family's paged
path whose shapes the traffic can reach: one page program per context
length, one fused step per (batch, pages, pool rows) bucket, and the pool's
row write per capacity (a pool that first crosses a capacity inside the
window would build that one there).

The shapes are data: ``warm.prefill_context_pages``, ``warm.fused_buckets``
and ``warm.pool_rows`` in the traffic file, found by a census of the
schedule (``census_latent_moe.py``). The calls mirror the engine's through
``cfg.paged_family`` argument for argument; what is missed compiles in the
warm-up requests or shows in ``entry.window_compiles``.
"""

from __future__ import annotations


def warm(engine, cfg, params, spec: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = cfg.paged_family
    P = engine.page_tokens
    dt = jnp.dtype(cfg.dtype)

    def leaves(batch: int, tokens: int = P) -> tuple:
        shape = fam.leaf_shape(cfg, tokens, batch)
        return tuple(jnp.zeros(shape, dt) for _ in range(fam.n_leaves))

    def rows(n: int) -> tuple:
        shape = fam.leaf_shape(cfg, P)
        shape = (n, shape[0]) + shape[2:]
        return tuple(jnp.zeros(shape, dt) for _ in range(fam.n_leaves))

    out = []
    for pages in range(int(spec.get("prefill_context_pages", 0))):
        out.append(fam.page(
            params, jnp.zeros((1, P), jnp.int32),
            jnp.asarray([pages * P, 0], jnp.int32),
            leaves(1, pages * P), leaves(1), cfg))
    for b, mp, n in spec.get("fused_buckets", []):
        out.append(fam.step(
            params, jnp.zeros((b,), jnp.int32), jnp.zeros((b, 4), jnp.int32),
            b, rows(n), jnp.zeros((b, mp), jnp.int32), leaves(b), cfg))
    for n in spec.get("pool_rows", []):
        out.append(fam.write_row(rows(n), leaves(1), np.int32(0)))
    jax.block_until_ready(out)
