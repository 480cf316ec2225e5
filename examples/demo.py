"""End-to-end oncilla-tpu walkthrough — runnable on any machine.

Covers the reference's user journey (alloc → localbuf → one-sided
put/get → copy → free; /root/reference/test/ocm_test.c) plus what this
framework adds on top: an in-process 2-node cluster, a training
checkpoint into the other node's DRAM, and a paged-KV decode.

Run (from the repo root):
      python examples/demo.py            # CPU is fine (fake cluster)
      JAX_PLATFORMS=cpu python examples/demo.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    # Force the CPU platform with 8 virtual devices so the sharded
    # sections demo a real mesh.
    from oncilla_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(8)

import oncilla_tpu as ocm
from oncilla_tpu import OcmKind


def local_memory():
    print("== 1. Local allocations (ocm_test.c test 1/2 shape) ==")
    # Ocm is a context manager: leaving the block runs tini(), which
    # reclaims any handle the app forgot (and — with OCM_ALLOCTRACE=1 —
    # reports each leak's allocation site).
    with ocm.ocm_init(ocm.OcmConfig(
        host_arena_bytes=32 << 20, device_arena_bytes=32 << 20,
    )) as ctx:
        h = ctx.alloc(1 << 20, OcmKind.LOCAL_DEVICE)
        data = np.random.default_rng(0).integers(
            0, 256, 1 << 20, dtype=np.uint8
        )
        ctx.put(h, data)                       # one-sided write
        back = np.asarray(ctx.get(h))          # one-sided read
        assert np.array_equal(back, data)
        print(f"   put/get {h.nbytes >> 10} KiB on {h.kind.name}: "
              "roundtrip ok")

        h2 = ctx.alloc(1 << 20, OcmKind.LOCAL_HOST)
        ctx.copy(h2, h)                        # kind×kind copy matrix
        assert np.array_equal(np.asarray(ctx.get(h2)), data)
        print("   device->host ocm_copy: ok")
        ctx.free(h), ctx.free(h2)


def cluster_and_checkpoint():
    print("== 2. Two-node cluster: remote DRAM + training checkpoint ==")
    from oncilla_tpu.models import checkpoint as ckpt
    from oncilla_tpu.runtime.cluster import local_cluster

    cfg = ocm.OcmConfig(
        host_arena_bytes=16 << 20, device_arena_bytes=1 << 20,
        chunk_bytes=256 << 10, heartbeat_s=0.5, lease_s=30.0,
    )
    with local_cluster(2, config=cfg) as cluster:
        ctx = cluster.context(0)
        h = ctx.alloc(2 << 20, OcmKind.REMOTE_HOST)
        print(f"   alloc placed on rank {h.rank} "
              f"(origin 0; is_remote={h.is_remote})")
        payload = np.arange(2 << 20, dtype=np.uint8)
        ctx.put(h, payload)
        assert np.array_equal(np.asarray(ctx.get(h)), payload)
        print("   one-sided put/get across the (loopback) DCN fabric: ok")
        ctx.free(h)

        # A small "train state" checkpointed into the other node's memory.
        state = {
            "w": jnp.asarray(np.random.default_rng(1).standard_normal(
                (256, 128)), jnp.bfloat16),
            "step": jnp.int32(1234),
        }
        hc = ckpt.save(ctx, state, OcmKind.REMOTE_HOST)
        restored = ckpt.load(ctx, hc, like=state)
        assert int(restored["step"]) == 1234
        print(f"   checkpoint ({hc.nbytes >> 10} KiB) saved to rank "
              f"{hc.rank} DRAM and restored: ok")
        ctx.free(hc)


def model_and_paged_decode():
    print("== 3. Flagship model: train step + OCM-paged decode ==")
    from oncilla_tpu.models import llama, train
    from oncilla_tpu.models.kv_paging import BucketedPagedDecoder

    cfg = llama.LlamaConfig.tiny()
    mesh = train.make_mesh()  # uses every visible device
    params, opt_state, tx = train.make_train_state(
        jax.random.key(0), cfg, mesh, lr=1e-2
    )
    step = train.make_train_step(cfg, mesh, tx)
    tokens = jax.device_put(
        train.sample_batch(np.random.default_rng(2), cfg, 4, 32),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )
    for i in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
    print(f"   3 sharded train steps on mesh {dict(mesh.shape)}: "
          f"loss={float(loss):.3f}")

    with ocm.ocm_init(ocm.OcmConfig(
        host_arena_bytes=16 << 20, device_arena_bytes=4 << 20,
    )) as ctx:
        dec = BucketedPagedDecoder(
            params, cfg, ctx, batch=1, page_tokens=8,
            kind=OcmKind.LOCAL_HOST, dtype="float32",
        )
        ids = np.random.default_rng(3).integers(
            0, cfg.vocab, 24, dtype=np.int32
        )
        logits = None
        for t in ids:
            logits = dec.step(jnp.asarray([t]))
        print(f"   24 decode steps, KV paged through OCM "
              f"({len(dec.cache.pages)} pages shipped): logits {logits.shape}")
        dec.close()


if __name__ == "__main__":
    local_memory()
    cluster_and_checkpoint()
    model_and_paged_decode()
    print("demo complete")
