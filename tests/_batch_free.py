"""What ``DeviceArena.free_many`` has to hold in either arena layout: the
checks shared by ``test_arena.py`` (a flat arena) and ``test_hbm_blocked.py``
(a blocked one)."""

import numpy as np
import pytest

from oncilla_tpu.core.arena import ArenaAllocator, Extent
from oncilla_tpu.core.errors import OcmInvalidHandle
from oncilla_tpu.core.hbm import _SCRUB_GROUPS, _pow2_chunks

#: Extents freed together in the parametrised tests: one, a padded small
#: group, exactly the largest group, one past it, and many groups.
COUNTS = (1, 3, 64, 65, 400)


def freed_one_by_one(arena, extents) -> ArenaAllocator:
    """An allocator in the state of ``arena``'s, after ``extents`` were
    freed one at a time."""
    ref = ArenaAllocator(arena.capacity, arena.allocator.alignment)
    ref._free = list(arena.allocator._free)
    ref._live = dict(arena.allocator._live)
    for extent in extents:
        ref.free(extent)
    return ref


def fill(arena, extent, rng) -> np.ndarray:
    data = rng.integers(1, 256, extent.nbytes, dtype=np.uint8)
    arena.write(extent, data)
    return data


def check_free_many(arena, size: int, n: int, rng) -> None:
    """``n`` extents of ``size`` (a size the arena has been prepared for),
    neighbours between the first of them: freed together they take a
    dispatch a group, read zeros when next allocated, leave the neighbours'
    bytes alone and the allocator as ``n`` single frees leave it."""
    assert arena.prepare_scrub(size)
    victims, kept = [], []
    for i in range(n):
        victims.append(arena.alloc(size))
        fill(arena, victims[-1], rng)
        if i < 6:
            keeper = arena.alloc(size)
            kept.append((keeper, fill(arena, keeper, rng)))
    want = freed_one_by_one(arena, victims)
    # freed in another order than they were allocated
    order = [victims[i] for i in rng.permutation(n)]
    dispatches = arena.free_many(order)
    assert dispatches == -(-n // _SCRUB_GROUPS[-1])
    assert arena.allocator._free == want._free
    assert arena.allocator._live == want._live
    assert arena.allocator.bytes_live == want.bytes_live
    again = [arena.alloc(size) for _ in range(n)]
    assert {e.offset for e in again} == {e.offset for e in victims}
    for extent in again:
        assert not np.asarray(arena.read(extent, size)).any()
    for keeper, data in kept:
        np.testing.assert_array_equal(
            np.asarray(arena.read(keeper, size)), data)
    assert arena.free_many(again + [k for k, _ in kept]) >= 1
    assert arena.allocator.bytes_live == want.bytes_live - len(kept) * size


def check_refusals(arena, size: int, rng) -> None:
    """A double free, an extent listed twice and a foreign extent raise,
    and nothing is scrubbed or released."""
    assert arena.prepare_scrub(size)
    a, b = arena.alloc(size), arena.alloc(size)
    data = [fill(arena, a, rng), fill(arena, b, rng)]
    gone = arena.alloc(size)
    arena.free_many([gone])
    live = arena.allocator.bytes_live
    foreign = Extent(b.offset + size + 512, size)
    for batch in ([a, gone], [a, b, a], [b, foreign],
                  [Extent(a.offset, 2 * size)]):
        with pytest.raises(OcmInvalidHandle):
            arena.free_many(batch)
        assert arena.allocator.bytes_live == live
        for extent, want in zip((a, b), data):
            np.testing.assert_array_equal(
                np.asarray(arena.read(extent, size)), want)
    arena.free_many([a, b])


def check_mixed_sizes(arena, size: int, other: int, rng) -> None:
    """Sizes the arena was not prepared for go down the path that stands,
    a power of two a dispatch, beside a prepared size's groups; every
    extent reads zeros afterwards."""
    assert arena.prepare_scrub(size) and other not in arena._scrub_sizes
    extents = [arena.alloc(s) for s in (size, other, size, other, size)]
    for extent in extents:
        fill(arena, extent, rng)
    unit = arena.buffer.shape[-1] if arena.buffer.ndim == 2 else 1
    assert other % unit == 0
    per_other = len(_pow2_chunks(other // unit, 1 << 40))
    assert arena.free_many(extents) == 1 + 2 * per_other
    again = [arena.alloc(s) for s in (size, other, size, other, size)]
    for extent in again:
        assert not np.asarray(arena.read(extent, extent.nbytes)).any()
    arena.free_many(again)
