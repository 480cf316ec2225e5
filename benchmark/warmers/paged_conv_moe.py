"""Compile, before the window, the programs of the ``conv_gqa_moe``
family's paged path whose shapes the traffic can reach: the page program a
padded context (the family pads a context to a power-of-two number of pages
and joins the pages in one dispatch, so ``warm.prefill_padded_pages`` lists
the handful of sizes and not a context length each), and one fused step per
(batch, pages, pool rows) bucket.

The shapes are data, found by a census of the schedule
(``census_conv_moe.py``). The calls mirror the engine's through
``cfg.paged_family`` argument for argument, the carry among them; the pool's
own programs are the engine's, which warms them when a capacity is first
reached; what is missed compiles in the warm-up requests or shows in
``entry.window_compiles``.
"""

from __future__ import annotations


def warm(engine, cfg, params, spec: dict) -> None:
    import jax
    import jax.numpy as jnp

    fam = cfg.paged_family
    P = engine.page_tokens
    dt = jnp.dtype(cfg.dtype)

    def leaves(batch: int) -> tuple:
        shape = fam.leaf_shape(cfg, P, batch)
        return tuple(jnp.zeros(shape, dt) for _ in range(fam.n_leaves))

    def carry(batch: int) -> tuple:
        return tuple(jnp.zeros(shape, t)
                     for shape, t in fam.carry_leaves(cfg, batch))

    def rows(n: int) -> tuple:
        shape = fam.leaf_shape(cfg, P)
        shape = (n, shape[0]) + shape[2:]
        return tuple(jnp.zeros(shape, dt) for _ in range(fam.n_leaves))

    done = jax.block_until_ready
    for pages in spec.get("prefill_padded_pages", []):
        # As ServingEngine._context joins a session's pages: the family's
        # own join, which pads to the power of two above.
        ctx = fam.context([[leaves(1)] * int(pages)], cfg, P)
        done(fam.page(
            params, jnp.zeros((1, P), jnp.int32),
            jnp.asarray([int(pages) * P, 0], jnp.int32), ctx, leaves(1), cfg,
            carry(1)))
    for b, mp, n in spec.get("fused_buckets", []):
        done(fam.step(
            params, jnp.zeros((b,), jnp.int32), jnp.zeros((b, 4), jnp.int32),
            b, rows(n), jnp.zeros((b, mp), jnp.int32), leaves(b), cfg,
            carry(b)))
