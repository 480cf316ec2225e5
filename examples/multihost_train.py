"""Multi-process SPMD training + cross-host OCM checkpoint.

The real multi-host shape, runnable anywhere: N OS processes (one per
"host", here all on localhost) form ONE global JAX mesh via
``jax.distributed``, and the SAME train-step factories used single-chip
(`models/train.py`) run unchanged over it — GSPMD lays dp/tp/sp
collectives over the global device set, exactly how a v5p pod slice is
driven (ICI collectives intra-slice, DCN across; the reference scales via
per-host daemons + NCCL/MPI-style fabrics, SURVEY.md §1/§5.8).

Alongside the mesh, each process attaches to its per-host oncilla daemon
(the nodefile names one per process) and the train state is checkpointed
into a REMOTE_HOST OCM allocation — process 0 writes it through its
daemon into rank 1's arena, and EVERY process reads it back one-sided and
verifies byte equality (models/checkpoint.py packing).

Usage (see multihost_train.sh for the self-contained launcher):
    python examples/multihost_train.py PROC_ID NPROCS COORD_PORT NODEFILE
"""

import os
import sys

sys.path.insert(0, ".")

LOCAL_DEVICES = 4


def main() -> int:
    proc_id, nprocs, coord_port, nodefile = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
    )
    # CPU platform with N virtual devices, WITHOUT initializing a backend
    # (jax.distributed.initialize must run first): env + config only —
    # force_cpu_devices would query devices.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={LOCAL_DEVICES}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{coord_port}", num_processes=nprocs, process_id=proc_id
    )
    assert jax.device_count() == nprocs * LOCAL_DEVICES

    import numpy as np
    from jax.sharding import NamedSharding

    import oncilla_tpu as ocm
    from oncilla_tpu.models import checkpoint, llama, train

    cfg = llama.LlamaConfig(
        vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        ffn_hidden=128, max_seq=64, dtype="float32",
    )
    mesh = train.make_mesh()  # global: all processes' devices
    # Deterministic numpy init => every process builds identical host
    # params; device_put under the global specs makes them ONE logical
    # sharded array across processes.
    params, opt_state, tx = train.make_train_state_host(0, cfg, mesh)
    step = train.make_train_step(cfg, mesh, tx)

    dp = dict(mesh.shape)[train.DP]
    sp = dict(mesh.shape)[train.SP]
    batch, seq = max(2 * dp, 2), 16 * max(sp, 1)
    rng = np.random.default_rng(0)  # same stream everywhere
    global_tokens = train.sample_batch(rng, cfg, batch, seq)
    # Each process contributes its slice of the global batch.
    tokens = jax.make_array_from_process_local_data(
        NamedSharding(mesh, train.data_spec()),
        global_tokens[
            proc_id * batch // nprocs:(proc_id + 1) * batch // nprocs
        ],
        global_tokens.shape,
    )

    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))  # replicated scalar: same on every proc
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    print(f"proc {proc_id}: mesh={dict(mesh.shape)} losses={losses}",
          flush=True)

    # -- checkpoint through the per-host daemons ------------------------
    from jax.experimental import multihost_utils

    full = multihost_utils.process_allgather(params, tiled=True)
    ctx = ocm.ocm_init(ocm.OcmConfig(
        nodefile=nodefile, rank=proc_id,
        host_arena_bytes=64 << 20, device_arena_bytes=1 << 20,
    ))
    if proc_id == 0:
        h = checkpoint.save(ctx, full, kind=ocm.OcmKind.REMOTE_HOST)
        assert h.is_remote and h.rank == 1, (h.rank, h.is_remote)
        # Hand the one-sided address to the other processes via the mesh
        # (a tiny int32 broadcast — the handle IS connectionless).
        addr = np.array(
            [h.alloc_id & 0xFFFFFFFF, h.alloc_id >> 32, h.rank,
             h.extent.offset, h.nbytes], np.int64,
        )
    else:
        addr = np.zeros(5, np.int64)
    addr = multihost_utils.broadcast_one_to_all(addr)
    from oncilla_tpu.core.arena import Extent
    from oncilla_tpu.core.handle import OcmAlloc
    from oncilla_tpu.core.kinds import Fabric

    ghost = OcmAlloc(
        alloc_id=int(addr[0]) | (int(addr[1]) << 32),
        kind=ocm.OcmKind.REMOTE_HOST, fabric=Fabric.DCN,
        nbytes=int(addr[4]), rank=int(addr[2]), device_index=0,
        extent=Extent(offset=int(addr[3]), nbytes=int(addr[4])),
        origin_rank=0,
    )
    restored = checkpoint.load(ctx, ghost, like=full)
    for k in full:
        np.testing.assert_array_equal(
            np.asarray(full[k]), np.asarray(restored[k])
        )
    print(f"proc {proc_id}: checkpoint of {checkpoint.checkpoint_nbytes(full)}"
          f" B restored byte-exact from rank {ghost.rank}'s arena", flush=True)
    multihost_utils.sync_global_devices("ckpt-verified")
    if proc_id == 0:
        ctx.free(h)
    ocm.ocm_tini(ctx)
    print(f"proc {proc_id}: ok", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
