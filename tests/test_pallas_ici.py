"""Interpret-mode execution of the Pallas one-sided remote-DMA kernels.

The Pallas TPU interpret machine (``pltpu.InterpretParams``) simulates the
semaphore + DMA semantics on the virtual CPU mesh, so the exact kernel that
drives the hardware DMA engines on TPU — ``make_async_remote_copy`` with
send/recv semaphores, the analogue of ``ib_write``/``ib_read`` posting RDMA
work requests (/root/reference/src/rdma.c:47-85,241-263) — is executed by
CI, not just compiled. Covers the cases of the reference's one-sided tests
(/root/reference/test/ib_client.c:144-188, test/ocm_test.c:132-206):
pattern-stamp + readback, same-device, cross-device, and edge extents.
"""

import jax
import numpy as np
import pytest

from oncilla_tpu.ops import pallas_ici as pi
from oncilla_tpu.parallel import spmd_arena as sa
from oncilla_tpu.parallel.mesh import node_mesh

ARENA = 64 << 10          # per-device row: 16 blocks
NBLK = ARENA // pi.BLOCK


@pytest.fixture(scope="module")
def mesh():
    return node_mesh()


def _stamped_arena(mesh, rng):
    """Arena with every device row stamped with a distinct pattern."""
    arena = sa.make_arena(mesh, ARENA)
    rows = {}
    for d in range(mesh.devices.size):
        row = rng.integers(0, 256, ARENA, dtype=np.uint8)
        rows[d] = row
        arena = sa.host_put(arena, d, row, 0, mesh=mesh)
    return arena, rows


def test_cross_device_one_sided(mesh, rng):
    arena, rows = _stamped_arena(mesh, rng)
    nbytes = 2 * pi.BLOCK
    arena = pi.pallas_ici_copy(arena, 1, 6, 0, 4 * pi.BLOCK, nbytes, mesh=mesh)
    got = np.asarray(sa.host_get(arena, 6, nbytes, 4 * pi.BLOCK, mesh=mesh))
    np.testing.assert_array_equal(got, rows[1][:nbytes])
    # Source row intact; bystander rows untouched.
    np.testing.assert_array_equal(
        np.asarray(sa.host_get(arena, 1, ARENA, 0, mesh=mesh)), rows[1]
    )
    for d in (0, 2, 3, 5, 7):
        np.testing.assert_array_equal(
            np.asarray(sa.host_get(arena, d, ARENA, 0, mesh=mesh)), rows[d]
        )


def test_same_device_local_fast_path(mesh, rng):
    arena, rows = _stamped_arena(mesh, rng)
    nbytes = 3 * pi.BLOCK
    arena = pi.pallas_ici_copy(
        arena, 4, 4, 0, 8 * pi.BLOCK, nbytes, mesh=mesh
    )
    got = np.asarray(sa.host_get(arena, 4, nbytes, 8 * pi.BLOCK, mesh=mesh))
    np.testing.assert_array_equal(got, rows[4][:nbytes])


def test_loopback_remote_dma(mesh, rng):
    """force_remote routes a same-device copy through the full
    make_async_remote_copy machinery (send + recv semaphores) — the mode the
    single-chip bench uses to measure the one-sided fabric."""
    arena, rows = _stamped_arena(mesh, rng)
    nbytes = 2 * pi.BLOCK
    arena = pi.pallas_ici_copy(
        arena, 3, 3, pi.BLOCK, 10 * pi.BLOCK, nbytes, mesh=mesh,
        force_remote=True,
    )
    got = np.asarray(sa.host_get(arena, 3, nbytes, 10 * pi.BLOCK, mesh=mesh))
    np.testing.assert_array_equal(got, rows[3][pi.BLOCK: pi.BLOCK + nbytes])


def test_edge_blocks(mesh, rng):
    """First block -> last block: extents touching both ends of the row."""
    arena, rows = _stamped_arena(mesh, rng)
    last = (NBLK - 1) * pi.BLOCK
    arena = pi.pallas_ici_copy(arena, 0, 7, 0, last, pi.BLOCK, mesh=mesh)
    got = np.asarray(sa.host_get(arena, 7, pi.BLOCK, last, mesh=mesh))
    np.testing.assert_array_equal(got, rows[0][: pi.BLOCK])
    # The destination row up to the last block is untouched.
    np.testing.assert_array_equal(
        np.asarray(sa.host_get(arena, 7, last, 0, mesh=mesh)), rows[7][:last]
    )


def test_whole_row_transfer(mesh, rng):
    arena, rows = _stamped_arena(mesh, rng)
    arena = pi.pallas_ici_copy(arena, 2, 5, 0, 0, ARENA, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(sa.host_get(arena, 5, ARENA, 0, mesh=mesh)), rows[2]
    )


def test_unaligned_rejected(mesh):
    arena = sa.make_arena(mesh, ARENA)
    with pytest.raises(AssertionError, match="BLOCK-aligned"):
        pi.pallas_ici_copy(arena, 0, 1, 17, 0, pi.BLOCK, mesh=mesh)
    assert not pi.pallas_supported(0, 0, pi.BLOCK - 1)
    assert pi.pallas_supported(pi.BLOCK, 2 * pi.BLOCK, pi.BLOCK)


def test_local_copy_kernel(rng):
    """pallas_local_copy (the bench's single-chip DMA copy) in interpret
    mode: overlapped two-descriptor copy, non-overlapping extents."""
    total = 16 * pi.BLOCK
    buf = rng.integers(0, 256, total, dtype=np.uint8)
    x = jax.device_put(buf)
    y = np.asarray(
        pi.pallas_local_copy(x, 0, 8 * pi.BLOCK, 4 * pi.BLOCK)
    )
    np.testing.assert_array_equal(
        y[8 * pi.BLOCK: 12 * pi.BLOCK], buf[: 4 * pi.BLOCK]
    )
    np.testing.assert_array_equal(y[: 8 * pi.BLOCK], buf[: 8 * pi.BLOCK])

    with pytest.raises(AssertionError, match="overlapping"):
        pi.pallas_local_copy(x, 0, pi.BLOCK, 2 * pi.BLOCK)


def test_mib_scale_rows_and_transfer(mesh, rng):
    """MiB-scale arena rows + a 1 MiB transfer — the sizes that starved the
    interpret machine before the windowed path: the
    whole-arena kernel cannot hold a >=128 KiB ref off-TPU, so the copy
    runs as chunked <=96 KiB windows through the identical remote-DMA
    kernel semantics."""
    row = 4 << 20           # 4 MiB per device
    nbytes = 1 << 20        # 1 MiB transfer
    arena = sa.make_arena(mesh, row)
    pat = rng.integers(0, 256, nbytes, dtype=np.uint8)
    arena = sa.host_put(arena, 2, pat, 0, mesh=mesh)
    arena = pi.pallas_ici_copy(arena, 2, 5, 0, 2 << 20, nbytes, mesh=mesh)
    got = np.asarray(sa.host_get(arena, 5, nbytes, 2 << 20, mesh=mesh))
    np.testing.assert_array_equal(got, pat)


def test_window_chunk_boundary(mesh, rng):
    """A transfer that is not a multiple of the interpret window (24 + 6
    blocks) exercises the partial tail chunk; bystander bytes at both ends
    of the destination extent stay intact."""
    nblocks = pi.INTERP_WINDOW_BLOCKS + 6
    row = 64 * pi.BLOCK
    nbytes = nblocks * pi.BLOCK
    arena = sa.make_arena(mesh, row)
    base = rng.integers(0, 256, row, dtype=np.uint8)
    arena = sa.host_put(arena, 6, base, 0, mesh=mesh)
    pat = rng.integers(0, 256, nbytes, dtype=np.uint8)
    arena = sa.host_put(arena, 1, pat, 0, mesh=mesh)
    arena = pi.pallas_ici_copy(
        arena, 1, 6, 0, 8 * pi.BLOCK, nbytes, mesh=mesh
    )
    got = np.asarray(sa.host_get(arena, 6, row, 0, mesh=mesh))
    np.testing.assert_array_equal(got[8 * pi.BLOCK: 8 * pi.BLOCK + nbytes], pat)
    np.testing.assert_array_equal(got[: 8 * pi.BLOCK], base[: 8 * pi.BLOCK])
    np.testing.assert_array_equal(
        got[8 * pi.BLOCK + nbytes:], base[8 * pi.BLOCK + nbytes:]
    )


def test_fuzz_windowed_copies_against_numpy_model(mesh, rng):
    """Property test: a chain of one-sided copies must equal a numpy
    shadow model byte-for-byte. The chain FORCES the paths a fixed seed
    might miss — a multi-window transfer (> INTERP_WINDOW_BLOCKS, so the
    chunk loop's `+ done` offsets are on the hook), a same-device
    disjoint copy (local-DMA fast path), and a loopback force_remote copy
    (send/recv semaphore machinery) — then adds random cross-device
    routes on top."""
    row = 48 * pi.BLOCK
    nd = mesh.devices.size
    arena = sa.make_arena(mesh, row)
    shadow = np.zeros((nd, row), np.uint8)
    for d in range(nd):
        stamp = rng.integers(0, 256, row, dtype=np.uint8)
        shadow[d] = stamp
        arena = sa.host_put(arena, d, stamp, 0, mesh=mesh)

    win = pi.INTERP_WINDOW_BLOCKS
    cases = [
        # (s_dev, d_dev, s_blk, d_blk, nblk, force_remote)
        (1, 6, 2, 10, win + 5, False),   # multi-window chunking
        (3, 3, 0, 30, 12, False),        # same-device local fast path
        (5, 5, 20, 4, 9, True),          # loopback remote DMA
    ]
    draws = 0
    while draws < 8:
        s_dev, d_dev = int(rng.integers(nd)), int(rng.integers(nd))
        nblk = int(rng.integers(1, 31))
        s_blk = int(rng.integers(0, 48 - nblk + 1))
        d_blk = int(rng.integers(0, 48 - nblk + 1))
        if s_dev == d_dev and not (
            s_blk + nblk <= d_blk or d_blk + nblk <= s_blk
        ):
            continue  # re-draw: same-device extents must be disjoint
        cases.append((s_dev, d_dev, s_blk, d_blk, nblk, False))
        draws += 1

    assert any(c[4] > win for c in cases)          # multi-window present
    assert any(c[0] == c[1] and not c[5] for c in cases)
    assert any(c[5] for c in cases)                # loopback present
    for s_dev, d_dev, s_blk, d_blk, nblk, force in cases:
        n = nblk * pi.BLOCK
        arena = pi.pallas_ici_copy(
            arena, s_dev, d_dev, s_blk * pi.BLOCK, d_blk * pi.BLOCK, n,
            mesh=mesh, force_remote=force,
        )
        shadow[d_dev, d_blk * pi.BLOCK: d_blk * pi.BLOCK + n] = (
            shadow[s_dev, s_blk * pi.BLOCK: s_blk * pi.BLOCK + n]
        )
    for d in range(nd):
        np.testing.assert_array_equal(
            np.asarray(sa.host_get(arena, d, row, 0, mesh=mesh)), shadow[d],
            err_msg=f"device {d}",
        )
