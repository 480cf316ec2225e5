"""Arena suballocator unit tests."""

import numpy as np
import pytest

import _batch_free
from oncilla_tpu import ArenaAllocator, OcmInvalidHandle, OcmOutOfMemory
from oncilla_tpu.core.hbm import _SCRUB_GROUPS, DeviceArena, _scrub_groups


def test_alloc_free_roundtrip():
    a = ArenaAllocator(1 << 20, alignment=512)
    e = a.alloc(1000)
    assert e.offset == 0
    assert e.nbytes == 1000
    assert a.num_live == 1
    a.free(e)
    assert a.num_live == 0
    assert a.bytes_free == 1 << 20


def test_alignment():
    a = ArenaAllocator(1 << 20, alignment=512)
    e1 = a.alloc(1)
    e2 = a.alloc(1)
    assert e2.offset == 512
    assert e1.offset % 512 == 0


def test_oom():
    a = ArenaAllocator(4096)
    a.alloc(4096)
    with pytest.raises(OcmOutOfMemory):
        a.alloc(1)


def test_double_free_rejected():
    a = ArenaAllocator(4096)
    e = a.alloc(100)
    a.free(e)
    with pytest.raises(OcmInvalidHandle):
        a.free(e)


def test_coalescing_allows_full_realloc():
    a = ArenaAllocator(4096, alignment=512)
    es = [a.alloc(512) for _ in range(8)]
    # Free in interleaved order to exercise both coalesce directions.
    for i in [1, 3, 5, 7, 0, 2, 4, 6]:
        a.free(es[i])
    big = a.alloc(4096)
    assert big.offset == 0


def test_first_fit_reuses_hole():
    a = ArenaAllocator(1 << 16, alignment=512)
    e1 = a.alloc(512)
    a.alloc(512)
    a.free(e1)
    e3 = a.alloc(512)
    assert e3.offset == e1.offset


def test_fragmentation_reported_in_error():
    a = ArenaAllocator(2048, alignment=512)
    keep = [a.alloc(512) for _ in range(4)]
    a.free(keep[0])
    a.free(keep[2])
    with pytest.raises(OcmOutOfMemory):
        a.alloc(1024)  # 1024 free but split into two 512 holes


def test_invalid_args():
    with pytest.raises(ValueError):
        ArenaAllocator(0)
    with pytest.raises(ValueError):
        ArenaAllocator(100, alignment=3)
    a = ArenaAllocator(4096)
    with pytest.raises(ValueError):
        a.alloc(0)


# -- DeviceArena.free_many over a flat arena (the blocked layout:
# test_hbm_blocked.py) ----------------------------------------------------

PAGE = 36 << 10          # no power of two: two fills a page on the old path


@pytest.fixture(scope="module")
def flat_arena():
    return DeviceArena(420 * PAGE)


@pytest.mark.parametrize("n", _batch_free.COUNTS)
def test_free_many_scrubs_a_group_a_dispatch(flat_arena, rng, n):
    _batch_free.check_free_many(flat_arena, PAGE, n, rng)


def test_free_many_refuses_before_it_releases(flat_arena, rng):
    _batch_free.check_refusals(flat_arena, PAGE, rng)


def test_free_many_of_mixed_sizes_falls_back_and_scrubs(flat_arena, rng):
    _batch_free.check_mixed_sizes(flat_arena, PAGE, 5 << 10, rng)


@pytest.mark.parametrize("n, want", [
    (1, [1]), (3, [4]), (4, [4]), (5, [16]), (17, [64]), (64, [64]),
    (65, [64, 1]), (400, [64] * 6 + [16]),
])
def test_scrub_groups_cover_a_batch_with_one_padded_group(n, want):
    assert _scrub_groups(n) == want
    assert set(want) <= set(_SCRUB_GROUPS)


def test_prepare_scrub_leaves_the_arena_as_it_was(rng):
    """The group programs are run over a scratch extent that is free: live
    bytes and the allocator are untouched, and a full arena is refused (its
    extents are then freed the old way)."""
    arena = DeviceArena(4 * PAGE)
    held = [arena.alloc(PAGE) for _ in range(3)]
    data = [_batch_free.fill(arena, e, rng) for e in held]
    free_before = list(arena.allocator._free)
    assert arena.prepare_scrub(PAGE) and arena.prepare_scrub(PAGE)
    assert arena.allocator._free == free_before
    for extent, want in zip(held, data):
        np.testing.assert_array_equal(
            np.asarray(arena.read(extent, PAGE)), want)
    full = DeviceArena(2 * PAGE)
    both = [full.alloc(PAGE), full.alloc(PAGE)]
    assert not full.prepare_scrub(PAGE)
    assert full.free_many(both) == 4      # 32 + 4 KiB each: the old path
    assert full.allocator.bytes_live == 0
