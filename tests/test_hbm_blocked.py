"""Blocked (>2 GiB) device arenas: GB-scale regions with int32 tracing.

The reference registers 2-4 GiB buffers and sweeps transfers up to 1-4 GB
over them (/root/reference/test/ocm_test.c:329-330, test/ib_client.c:85-131);
DeviceArena supports the same scale via (nblocks, 4096) blocked addressing —
no JAX_ENABLE_X64, no int64 traced offsets.
"""

import numpy as np
import pytest

import _batch_free
from oncilla_tpu.core.hbm import _BLOCK, DeviceArena

GIB = 1 << 30
CAP = 2 * GIB + (4 << 20)  # just past the int32 cliff


@pytest.fixture(scope="module")
def big_arena():
    # ~2 GiB of host RAM on the CPU test backend; one per module.
    return DeviceArena(CAP)


def test_blocked_layout(big_arena):
    assert big_arena.buffer.shape == (CAP // _BLOCK, _BLOCK)
    assert big_arena.capacity == CAP


def test_write_read_beyond_int32(big_arena, rng):
    # An extent whose absolute offsets exceed 2**31 — the case the flat
    # int32 path cannot address.
    a = big_arena
    first = a.alloc(2 * GIB)      # pushes the next extent past the cliff
    ext = a.alloc(1 << 20)
    assert ext.offset + ext.nbytes > 2**31
    data = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
    a.write(ext, data)
    np.testing.assert_array_equal(np.asarray(a.read(ext, 1 << 20)), data)
    a.free(ext)
    a.free(first)


def test_write_many_beyond_int32(big_arena, rng):
    # Rows written a group at a time past the int32 cliff: one program,
    # addressed by block rows, the group padded to the rows' count.
    a = big_arena
    first = a.alloc(2 * GIB)
    exts = [a.alloc(8 * _BLOCK) for _ in range(3)]
    assert exts[-1].offset > 2**31
    rows = rng.integers(0, 256, (4, 8 * _BLOCK), dtype=np.uint8)
    assert a.write_many(exts, rows) == 1
    for i, ext in enumerate(exts):
        np.testing.assert_array_equal(
            np.asarray(a.read(ext, 8 * _BLOCK)), rows[i])
        a.free(ext)
    a.free(first)


def test_unaligned_window_write_read(big_arena, rng):
    # Byte ranges straddling block boundaries go through the window path.
    a = big_arena
    ext = a.alloc(64 << 10)
    n = 3 * _BLOCK + 513
    data = rng.integers(0, 256, n, dtype=np.uint8)
    a.write(ext, data, offset=_BLOCK - 257)   # crosses 4+ block boundaries
    got = np.asarray(a.read(ext, n, offset=_BLOCK - 257))
    np.testing.assert_array_equal(got, data)
    # Neighbouring bytes untouched.
    assert not np.any(np.asarray(a.read(ext, _BLOCK - 257, 0)))
    a.free(ext)


def test_blocked_move_aligned_and_unaligned(big_arena, rng):
    a = big_arena
    src = a.alloc(1 << 20)
    dst = a.alloc(1 << 20)
    data = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
    a.write(src, data)
    a.move(src, dst, 1 << 20)                       # block-aligned rows path
    np.testing.assert_array_equal(np.asarray(a.read(dst, 1 << 20)), data)
    a.move(src, dst, 999, src_offset=17, dst_offset=33)  # window path
    np.testing.assert_array_equal(
        np.asarray(a.read(dst, 999, 33)), data[17:17 + 999]
    )
    a.free(src)
    a.free(dst)


def test_small_arena_still_flat():
    a = DeviceArena(1 << 20)
    assert a.buffer.shape == (1 << 20,)


def test_dma_row_kernels_interpret(rng):
    """The Pallas row-granular read/write/move kernels that serve aligned
    multi-MiB extents on TPU (GB-scale reads must run at DMA
    speed, not XLA dynamic-slice speed), executed here under the interpret
    machine on both arena layouts."""
    from oncilla_tpu.ops import pallas_ici as pi

    buf = rng.integers(0, 256, 4 << 20, dtype=np.uint8)
    for shape in ((4 << 20,), ((4 << 20) // _BLOCK, _BLOCK)):
        import jax

        x = jax.device_put(buf.reshape(shape))
        got = np.asarray(pi.pallas_read_rows(x, 1 << 20, 2 << 20))
        np.testing.assert_array_equal(got, buf[1 << 20: 3 << 20])

        raw = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
        y = pi.pallas_write_rows(x, jax.device_put(raw), 2 << 20)
        assert y.shape == shape
        flat = np.asarray(y).reshape(-1)
        np.testing.assert_array_equal(flat[2 << 20: 3 << 20], raw)
        np.testing.assert_array_equal(flat[: 2 << 20], buf[: 2 << 20])

        z = pi.pallas_local_copy(jax.device_put(buf.reshape(shape)),
                                 0, 2 << 20, 1 << 20)
        assert z.shape == shape
        flat = np.asarray(z).reshape(-1)
        np.testing.assert_array_equal(flat[2 << 20: 3 << 20], buf[: 1 << 20])


def test_read_rows_loop_matches_single(rng):
    """pallas_read_rows_loop (the dispatch-amortized bench leg) returns
    the same bytes as a single pallas_read_rows for every k, on both
    arena layouts — k only folds dispatches, never changes the data."""
    import jax

    from oncilla_tpu.ops import pallas_ici as pi

    buf = rng.integers(0, 256, 2 << 20, dtype=np.uint8)
    for shape in ((2 << 20,), ((2 << 20) // _BLOCK, _BLOCK)):
        x = jax.device_put(buf.reshape(shape))
        want = buf[1 << 20: (1 << 20) + (512 << 10)]
        for k in (1, 3):
            got = np.asarray(
                pi.pallas_read_rows_loop(x, 1 << 20, 512 << 10, k)
            )
            np.testing.assert_array_equal(got, want)


def test_dma_routing_in_arena(monkeypatch, rng):
    """With the TPU gate forced open, DeviceArena routes aligned >=1 MiB
    extents through the DMA kernels (interpret machine here) and the
    results match the XLA path bit-for-bit."""
    import oncilla_tpu.core.hbm as hbm

    monkeypatch.setattr(hbm, "_on_tpu", lambda: True)
    a = DeviceArena(8 << 20, alignment=4096)
    ext = a.alloc(4 << 20)
    data = rng.integers(0, 256, 2 << 20, dtype=np.uint8)
    a.write(ext, data)                       # DMA write path
    got = np.asarray(a.read(ext, 2 << 20))   # DMA read path
    np.testing.assert_array_equal(got, data)

    dst = a.alloc(2 << 20)
    a.move(ext, dst, 1 << 20)                # DMA move path
    np.testing.assert_array_equal(
        np.asarray(a.read(dst, 1 << 20)), data[: 1 << 20]
    )
    # Unaligned tail still goes through the window/XLA path and sees the
    # same bytes.
    got = np.asarray(a.read(ext, 100, offset=17))
    np.testing.assert_array_equal(got, data[17:117])


def test_blocked_scrub_on_free(big_arena, rng):
    """Scrub-on-free at GB scale incl. past the int32 cliff and with
    unaligned head/tail: a freed extent's reused bytes read as zeros."""
    a = big_arena
    first = a.alloc(2 * GIB)
    ext = a.alloc(2 << 20)
    assert ext.offset + ext.nbytes > 2**31
    a.write(ext, rng.integers(1, 256, 2 << 20, dtype=np.uint8))
    a.free(ext)
    ext2 = a.alloc(2 << 20)
    assert ext2.offset == ext.offset  # first-fit reuses the hole
    assert not np.asarray(a.read(ext2, 2 << 20)).any()
    a.free(ext2)

    # Unaligned partial fill (head/tail path) leaves neighbors intact.
    ext3 = a.alloc(64 << 10)
    pat = rng.integers(1, 256, 64 << 10, dtype=np.uint8)
    a.write(ext3, pat)
    a.fill_zero(ext3, nbytes=5000, offset=1000)
    got = np.asarray(a.read(ext3, 64 << 10))
    assert not got[1000:6000].any()
    np.testing.assert_array_equal(got[:1000], pat[:1000])
    np.testing.assert_array_equal(got[6000:], pat[6000:])
    a.free(ext3)
    a.free(first)


# -- DeviceArena.free_many over the blocked layout (the flat one:
# test_arena.py) -----------------------------------------------------------

SLOT = 3 * _BLOCK        # whole blocks, no power of two


@pytest.mark.parametrize("n", _batch_free.COUNTS)
def test_blocked_free_many_scrubs_a_group_a_dispatch(big_arena, rng, n):
    _batch_free.check_free_many(big_arena, SLOT, n, rng)


def test_blocked_free_many_refuses_before_it_releases(big_arena, rng):
    _batch_free.check_refusals(big_arena, SLOT, rng)


def test_blocked_free_many_of_mixed_sizes_falls_back(big_arena, rng):
    _batch_free.check_mixed_sizes(big_arena, SLOT, 5 * _BLOCK, rng)


def test_blocked_free_many_off_a_block_goes_the_old_way(big_arena, rng):
    """A size that is not whole blocks cannot be prepared, and extents of
    a prepared size that do not start on a block are scrubbed one by one
    (head, whole rows, tail): they still read zeros."""
    a = big_arena
    assert not a.prepare_scrub(SLOT + 512)
    assert a.prepare_scrub(SLOT)
    shim = a.alloc(512)                  # pushes what follows off a block
    odd = [a.alloc(SLOT) for _ in range(3)]
    assert all(e.offset % _BLOCK for e in odd)
    for extent in odd:
        _batch_free.fill(a, extent, rng)
    assert a.free_many(odd) > 3
    again = [a.alloc(SLOT) for _ in range(3)]
    for extent in again:
        assert not np.asarray(a.read(extent, SLOT)).any()
    a.free_many(again + [shim])
    assert a.allocator.bytes_live == 0
