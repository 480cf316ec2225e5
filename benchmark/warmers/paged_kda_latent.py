"""Compile, before the window, the programs of the ``kda_latent_moe``
family's paged path whose shapes the traffic can reach: the page program
per context length (the family pads a context to a power-of-two number of
pages, so the lengths share a handful of executables, but each length has
its own small concatenate and pad), one fused step per (batch, pages, pool
rows) bucket, and the pool's row write per capacity.

The shapes are data: ``warm.prefill_context_pages``, ``warm.fused_buckets``
and ``warm.pool_rows`` in the traffic file, found by a census of the
schedule (``census_kda_latent.py``). The calls mirror the engine's through
``cfg.paged_family`` argument for argument, the carry among them; what is
missed compiles in the warm-up requests or shows in
``entry.window_compiles``.
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

    def carry(batch: int) -> tuple:
        return tuple(jnp.zeros(shape, t)
                     for shape, t in fam.carry_leaves(cfg, batch))

    def rows(n: int) -> tuple:
        shape = fam.leaf_shape(cfg, P)
        shape = (n, shape[0]) + shape[2:]
        return tuple(jnp.zeros(shape, dt) for _ in range(fam.n_leaves))

    # A result is waited for and dropped before the next call: a step's
    # carry stack is 0.8 GB at batch 64, and nine of them do not fit beside
    # the weights.
    done = jax.block_until_ready
    for pages in range(int(spec.get("prefill_context_pages", 0))):
        # As ServingEngine._context joins a session's pages.
        held = [leaves(1) for _ in range(pages)]
        ctx = (tuple(jnp.concatenate([a[i] for a in held], axis=3)
                     for i in range(fam.n_leaves)) if held else leaves(1, 0))
        done(fam.page(
            params, jnp.zeros((1, P), jnp.int32),
            jnp.asarray([pages * P, 0], jnp.int32), ctx, leaves(1), cfg,
            carry(1)))
    for b, mp, n in spec.get("fused_buckets", []):
        done(fam.step(
            params, jnp.zeros((b,), jnp.int32), jnp.zeros((b, 4), jnp.int32),
            b, rows(n), jnp.zeros((b, mp), jnp.int32), leaves(b), cfg,
            carry(b)))
    for n in spec.get("pool_rows", []):
        done(fam.write_row(rows(n), leaves(1), np.int32(0)))
