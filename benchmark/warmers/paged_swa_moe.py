"""Compile, before the window, the programs of the ``swa_gqa_moe`` family's
paged path whose shapes the traffic can reach: the page program and the join
that builds its context per context length (the family pads its full-kind
context to a power-of-two number of pages and its window-kind context to
one size, so the lengths share a handful of executables), one fused step per (batch, full-kind pages, full-kind pool rows,
window-kind pages, window-kind pool rows) bucket, and each kind's row write
per capacity.

The shapes are data: ``warm.prefill_context_pages``, ``warm.fused_buckets``
and ``warm.pool_rows`` (a list a kind) in the traffic file, found by a
census of the schedule (``census_swa_moe.py``). The calls mirror the
engine's through ``cfg.paged_family`` argument for argument; what is missed
compiles in the warm-up requests or shows in ``entry.window_compiles``.
"""

from __future__ import annotations


def warm(engine, cfg, params, spec: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = cfg.paged_family
    P = engine.page_tokens
    dt = jnp.dtype(cfg.dtype)
    kinds = fam.page_kinds(cfg)
    of_kind = fam.kind_leaves(cfg)    # each kind's leaves among the family's

    def leaves(batch: int, tokens: int = P) -> tuple:
        return tuple(jnp.zeros(shape, dt)
                     for shape in fam.leaf_shapes(cfg, tokens, batch))

    def rows(k: int, n: int) -> tuple:
        return tuple(jnp.zeros((n, shape[0]) + shape[2:], dt)
                     for shape in fam.leaf_shapes(cfg, P)[of_kind[k]])

    done = jax.block_until_ready
    most = [None if kind.window is None else -(-kind.window // P)
            for kind in kinds]
    for pages in range(int(spec.get("prefill_context_pages", 0))):
        # As ServingEngine._context hands the family a session's pages: a
        # kind with a window holds its last pages only, and says where
        # they start.
        held, meta = [], [pages * P]
        for k, sl in enumerate(of_kind):
            n = pages if most[k] is None else min(pages, most[k])
            meta.append((pages - n) * P)
            held.append([leaves(1)[sl]] * n)
        done(fam.page(params, jnp.zeros((1, P), jnp.int32),
                      jnp.asarray(meta, jnp.int32),
                      fam.context(held, cfg, P), leaves(1), cfg))
    for b, *per_kind in spec.get("fused_buckets", []):
        # (batch, then a kind: table pages, pool rows).
        pool, tables = [], []
        for k in range(len(kinds)):
            mp, n = per_kind[2 * k], per_kind[2 * k + 1]
            pool += rows(k, n)
            tables.append(jnp.zeros((b, mp), jnp.int32))
        done(fam.step(
            params, jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, 2 + 2 * len(kinds)), jnp.int32), b, tuple(pool),
            tuple(tables), leaves(b), cfg))
    for k, capacities in enumerate(spec.get("pool_rows", [])):
        for n in capacities:
            done(fam.write_row(rows(k, n), leaves(1)[of_kind[k]],
                               np.int32(0)))
