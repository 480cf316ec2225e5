"""HBM bandwidth ceiling probe — the rerunnable evidence behind the copy
bandwidth headline.

BASELINE.md's transplanted target is 80 % of the v5e chip's 819 GB/s HBM
figure; the bench headline (extent-to-extent arena copies) lands ~0.88 of
that. This module turns the ceiling argument from a docstring claim into a
measurement: a copy's read+write turnaround keeps HBM
below the *read-only* line rate that the 819 figure describes, and no
descriptor scheme recovers it. Three probes, all on the real chip:

1. :func:`hbm_read_gbps` — a read-ONLY stream: the DMA engine pulls HBM
   chunks into a VMEM scratch (on-chip, no HBM write-back), double-buffered.
   HBM sees pure reads, so this approaches the quoted line rate and bounds
   everything else from above.
2. :func:`copy_gbps` — HBM→HBM extent copies with N persistent in-flight
   descriptor streams (the bench's scheme, parameterized to 1/2/4/8): shows
   the plateau is stream-count-independent — the engine saturates, more
   queue depth adds nothing.
3. :func:`vmem_roundtrip_gbps` — the same copy staged through VMEM
   (HBM→VMEM→HBM): strictly worse than the direct descriptor, evidence the
   direct DMA is the right scheme, not a missed optimization.

The measurement *shape* matches the reference's bandwidth harnesses
(size-held, iteration-timed, separate passes —
/root/reference/test/ocm_test.c:362-402); accounting follows the bench: a
copy is credited 2·nbytes of HBM traffic (read + write), the read-only
stream 1·nbytes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 4096


def _sync(b) -> None:
    jax.block_until_ready(b)


def _fresh(total_bytes: int) -> jax.Array:
    """A freshly transferred buffer (the HBM placement the DMA engine
    sustains best — see core/hbm.py arena materialization note)."""
    return jax.device_put(np.zeros(total_bytes, dtype=np.uint8))


def _interpret():
    from oncilla_tpu.ops.pallas_ici import _interpret_arg, _interpret_mode

    return _interpret_arg(_interpret_mode())


def _read_stream_loop(total_bytes: int, chunk_bytes: int, iters: int):
    """DMA every chunk of the buffer into a 2-deep VMEM scratch ring,
    ``iters`` sweeps, next chunk's descriptor posted before waiting the
    current one (double-buffered — the extoll.c:44-51 scheme)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert total_bytes % chunk_bytes == 0 and chunk_bytes % BLOCK == 0
    nchunks = total_bytes // chunk_bytes
    cb = chunk_bytes // BLOCK
    total = iters * nchunks

    def kernel(buf_in, buf_out, scratch, sems):
        del buf_in  # aliased; the kernel only reads buf_out

        def dma(i):
            c = jax.lax.rem(i, nchunks)
            return pltpu.make_async_copy(
                buf_out.at[pl.ds(c * cb, cb)],
                scratch.at[jax.lax.rem(i, 2)],
                sems.at[jax.lax.rem(i, 2)],
            )

        dma(0).start()

        def body(i, _):
            dma(i + 1).start()
            dma(i).wait()
            return 0

        jax.lax.fori_loop(0, total - 1, body, 0)
        dma(total - 1).wait()

    call = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, cb, 32, 128), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=jax.ShapeDtypeStruct((total_bytes // BLOCK, 32, 128), jnp.uint8),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=_interpret(),
    )

    def run(b):
        return call(b.reshape(-1, 32, 128)).reshape(total_bytes)

    return jax.jit(run, donate_argnums=0)


def hbm_read_gbps(
    total_bytes: int = 256 << 20, chunk_bytes: int = 2 << 20, iters: int = 600
) -> float:
    """Read-only HBM stream rate (GB/s of HBM read traffic).

    ``iters`` must put the device time well past the dispatch + sync
    latency: 8 sweeps (~2 GiB, ~3 ms of engine time) measure the
    dispatch, not HBM. 600 sweeps ≈ 157 GB ≈ 0.2+ s of engine time."""
    run = _read_stream_loop(total_bytes, chunk_bytes, iters)
    buf = _fresh(total_bytes)
    buf = run(buf)
    buf = run(buf)  # steady-state layouts after donation
    _sync(buf)
    t0 = time.perf_counter()
    buf = run(buf)
    _sync(buf)
    dt = time.perf_counter() - t0
    return total_bytes * iters / dt / 1e9


def _copy_stream_loop(total_bytes: int, nbytes: int, iters: int, streams: int):
    """N persistent descriptor streams ping-ponging disjoint segment pairs
    (the bench.py scheme, stream count parameterized)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nblocks = nbytes // BLOCK
    assert nblocks % (2 * streams) == 0
    # The ping-pong segment pairs span 2*nbytes of the buffer; anything
    # smaller would emit out-of-bounds DMA descriptors.
    assert total_bytes >= 2 * nbytes, (total_bytes, nbytes)
    q = nblocks // streams

    def kernel(buf_in, buf_out, sems):
        del buf_in

        def dma(stream, i):
            fwd = i % 2 == 0
            base = stream * 2 * q
            src = base + jnp.where(fwd, 0, q)
            dst = base + jnp.where(fwd, q, 0)
            return pltpu.make_async_copy(
                buf_out.at[pl.ds(src, q)],
                buf_out.at[pl.ds(dst, q)],
                sems.at[stream],
            )

        for s in range(streams):
            dma(s, 0).start()

        def body(i, _):
            for s in range(streams):
                dma(s, i).wait()
                dma(s, i + 1).start()
            return 0

        jax.lax.fori_loop(0, iters - 1, body, 0)
        for s in range(streams):
            dma(s, iters - 1).wait()

    call = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((streams,))],
        out_shape=jax.ShapeDtypeStruct((total_bytes // BLOCK, 32, 128), jnp.uint8),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=_interpret(),
    )

    def run(b):
        return call(b.reshape(-1, 32, 128)).reshape(total_bytes)

    return jax.jit(run, donate_argnums=0)


def copy_gbps(
    streams: int,
    total_bytes: int = 128 << 20,
    nbytes: int = 64 << 20,
    iters: int = 2000,
) -> float:
    """HBM→HBM copy traffic (2·nbytes per iteration) with ``streams``
    persistent in-flight descriptors. 2000 iterations matches the bench
    headline loop, so the sync at the end is a small share of the timed
    window."""
    run = _copy_stream_loop(total_bytes, nbytes, iters, streams)
    buf = _fresh(total_bytes)
    buf = run(buf)
    buf = run(buf)
    _sync(buf)
    t0 = time.perf_counter()
    buf = run(buf)
    _sync(buf)
    dt = time.perf_counter() - t0
    return 2.0 * nbytes * iters / dt / 1e9


def _vmem_roundtrip_loop(total_bytes: int, nbytes: int, iters: int,
                         chunk_bytes: int = 2 << 20):
    """The same ping-pong extent copy, but every chunk staged HBM→VMEM→HBM
    (two DMA hops per byte)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nblocks = nbytes // BLOCK
    cb = chunk_bytes // BLOCK
    assert nblocks % (2 * cb) == 0
    assert total_bytes >= 2 * nbytes, (total_bytes, nbytes)
    nchunks = nblocks // cb

    def kernel(buf_in, buf_out, scratch, sems):
        del buf_in

        def leg(i, c):
            """Chunk c of iteration i: in HBM→VMEM, then VMEM→HBM."""
            fwd = i % 2 == 0
            src = jnp.where(fwd, 0, nblocks) + c * cb
            dst = jnp.where(fwd, nblocks, 0) + c * cb
            slot = jax.lax.rem(c, 2)
            down = pltpu.make_async_copy(
                buf_out.at[pl.ds(src, cb)], scratch.at[slot], sems.at[slot]
            )
            up = pltpu.make_async_copy(
                scratch.at[slot], buf_out.at[pl.ds(dst, cb)], sems.at[2 + slot]
            )
            return down, up

        def body(i, _):
            def chunk_body(c, _):
                down, up = leg(i, c)
                down.start()
                down.wait()
                up.start()
                up.wait()
                return 0

            return jax.lax.fori_loop(0, nchunks, chunk_body, 0)

        jax.lax.fori_loop(0, iters, body, 0)

    call = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, cb, 32, 128), jnp.uint8),
            pltpu.SemaphoreType.DMA((4,)),
        ],
        out_shape=jax.ShapeDtypeStruct((total_bytes // BLOCK, 32, 128), jnp.uint8),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=_interpret(),
    )

    def run(b):
        return call(b.reshape(-1, 32, 128)).reshape(total_bytes)

    return jax.jit(run, donate_argnums=0)


def vmem_roundtrip_gbps(
    total_bytes: int = 128 << 20, nbytes: int = 64 << 20, iters: int = 400,
    chunk_bytes: int = 2 << 20,
) -> float:
    """Copy traffic (2·nbytes per iteration of HBM read+write) when staged
    through VMEM."""
    run = _vmem_roundtrip_loop(total_bytes, nbytes, iters, chunk_bytes)
    buf = _fresh(total_bytes)
    buf = run(buf)
    buf = run(buf)
    _sync(buf)
    t0 = time.perf_counter()
    buf = run(buf)
    _sync(buf)
    dt = time.perf_counter() - t0
    return 2.0 * nbytes * iters / dt / 1e9


def ceiling_probe(deadline: float | None = None) -> dict:
    """All three probes; with ``deadline`` (time.monotonic()), later stages
    are skipped (marked -1) once it passes — partial evidence beats none."""
    out: dict = {}

    def left() -> float:
        return float("inf") if deadline is None else deadline - time.monotonic()

    out["read_only_gbps"] = round(hbm_read_gbps(), 2)
    out["copy_streams_gbps"] = {}
    for s in (1, 2, 4, 8):
        if left() < 45:
            out["copy_streams_gbps"][str(s)] = -1.0
            continue
        out["copy_streams_gbps"][str(s)] = round(copy_gbps(s), 2)
    out["vmem_roundtrip_gbps"] = (
        round(vmem_roundtrip_gbps(), 2) if left() >= 45 else -1.0
    )
    return out


def main() -> None:
    import json

    print(json.dumps(ceiling_probe()))


if __name__ == "__main__":
    main()
