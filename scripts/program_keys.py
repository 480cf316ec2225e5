#!/usr/bin/env python3
"""Hash the canonical IR of every program the served families dispatch, at
tiny shapes on the CPU: the check, without a chip, that a change leaves the
programs of the benchmark's accepted cells alone.

JAX's persistent compilation cache keys an executable by its module with
the debug info stripped (``jax._src.cache_key``), so a moved line changes
nothing and a changed operation does. Run it on two trees and compare:

    git archive <parent> | tar -x -C /tmp/parent
    python3 scripts/program_keys.py /tmp/parent > a.json
    python3 scripts/program_keys.py . > b.json && diff a.json b.json

Equal hashes here mean equal programs for these shapes and configs; the
chip's cache is keyed by the same module at the cell's shapes.
"""

import hashlib
import json
import os
import sys

root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else ".")
sys.path.insert(0, root)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax._src import cache_key  # noqa: E402

from oncilla_tpu.models import kda_latent as kl  # noqa: E402
from oncilla_tpu.models import kv_paging, llama  # noqa: E402
from oncilla_tpu.models import latent_moe as lm  # noqa: E402
from oncilla_tpu.models import swa_moe as sm  # noqa: E402
from oncilla_tpu.serving import engine as eng  # noqa: E402

P, B, MP, N = 4, 2, 2, 4
i32 = jnp.int32


def key(lowered) -> str:
    module = lowered.compiler_ir("stablehlo")
    return hashlib.sha256(cache_key._canonicalize_ir(
        module, cache_key.IgnoreCallbacks.NO)).hexdigest()[:16]


def z(shape, dt=jnp.float32):
    return jnp.zeros(shape, dt)


out = {}
cfg = llama.LlamaConfig.tiny()
params = llama.init_params(jax.random.key(0), cfg)
L, KV, Hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
row, tail = (N, L, KV, P, Hd), (L, B, KV, P, Hd)
out["dense.step"] = key(kv_paging.paged_decode_batch_step_jit.lower(
    params, z((B,), i32), z((B, 4), i32), z(row), z(row), z((B, MP), i32),
    z(tail), z(tail), cfg))
out["dense.page"] = key(kv_paging.paged_decode_page_jit.lower(
    params, z((1, P), i32), z((2,), i32), z((L, 1, KV, 2 * P, Hd)),
    z((L, 1, KV, 2 * P, Hd)), z((L, 1, KV, P, Hd)), z((L, 1, KV, P, Hd)),
    cfg))
out["dense.row"] = key(kv_paging.paged_pool_write_row_jit.lower(
    z(row), z(row), z((L, 1, KV, P, Hd)), z((L, 1, KV, P, Hd)), np.int32(0)))

c2 = lm.LatentMoeConfig.tiny()
p2 = lm.init_params(jax.random.key(0), c2)
W, L2 = c2.latent_width, c2.n_layers
out["latent.step"] = key(lm.latent_decode_batch_step_jit.lower(
    p2, z((B,), i32), z((B, 4), i32), np.int32(B), z((N, L2, 1, P, W)),
    z((B, MP), i32), z((L2, B, 1, P, W)), c2))
out["latent.page"] = key(lm.latent_decode_page_jit.lower(
    p2, z((1, P), i32), z((2,), i32), z((L2, 1, 1, 2 * P, W)),
    z((L2, 1, 1, P, W)), c2))
out["latent.row"] = key(lm.latent_pool_write_row_jit.lower(
    z((N, L2, 1, P, W)), z((L2, 1, 1, P, W)), np.int32(0)))

c3 = kl.KdaLatentConfig.tiny()
p3 = kl.init_params(jax.random.key(0), c3)
Lm, W3 = len(c3.latent_layers), c3.latent_width


def carry(b):
    return tuple(z(s, d) for s, d in kl.PAGED_FAMILY.carry_leaves(c3, b))


out["kda.step"] = key(kl.kda_decode_batch_step_jit.lower(
    p3, z((B,), i32), z((B, 4), i32), np.int32(B), z((N, Lm, 1, P, W3)),
    z((B, MP), i32), z((Lm, B, 1, P, W3)), *carry(B), c3))
out["kda.page"] = key(kl.kda_decode_page_jit.lower(
    p3, z((1, P), i32), z((2,), i32), z((Lm, 1, 1, 2 * P, W3)),
    z((Lm, 1, 1, P, W3)), *carry(1), c3))

c4 = sm.SwaMoeConfig.tiny()
p4 = sm.init_params(jax.random.key(0), c4)


def swa(rows=None, batch=1, tokens=P):
    """The family's leaves, kind by kind: pool rows, or a tail or context."""
    return tuple(z((rows, s[0]) + s[2:]) if rows else z(s)
                 for s in sm.PAGED_FAMILY.leaf_shapes(c4, tokens, batch))


out["swa.step"] = key(sm.swa_decode_batch_step_jit.lower(
    p4, z((B,), i32), z((B, 6), i32), np.int32(B), swa(rows=N),
    (z((B, MP), i32), z((B, MP), i32)), swa(batch=B), c4))
out["swa.page"] = key(sm.swa_decode_page_jit.lower(
    p4, z((1, P), i32), z((3,), i32), swa(tokens=2 * P), swa(), c4))

stack = (z(tail), z(tail))
out["seat.write"] = key(eng._seat_write_jit.lower(
    stack, (z((L, 1, KV, P, Hd)),) * 2, np.int32(0)))
out["seat.move"] = key(eng._seat_move_jit.lower(
    stack, np.int32(0), np.int32(1)))
out["seat.read"] = key(eng._seat_read_jit.lower(stack, np.int32(0)))
# The pool's own two programs (PR 37; a tree from before has neither).
if hasattr(eng, "_pool_write_jit"):
    page = (z((L, 1, KV, P, Hd)),) * 2
    out["pool.write"] = key(eng._pool_write_jit.lower(
        (z(row), z(row)), (page,) * eng._POOL_GROUP,
        np.zeros(eng._POOL_GROUP, np.int32)))
    out["pool.gather"] = key(eng._pool_gather_jit.lower(
        (z(row), z(row)), np.zeros(2 * N, np.int32)))
# The window family's Mellum2 shape (PR 42; a tree from before lacks it).
if hasattr(sm.SwaMoeConfig, "tiny_softmax"):
    c6 = sm.SwaMoeConfig.tiny_softmax()
    p6 = sm.init_params(jax.random.key(0), c6)

    def mellum(rows=None, batch=1, tokens=P):
        return tuple(z((rows, s[0]) + s[2:]) if rows else z(s)
                     for s in sm.PAGED_FAMILY.leaf_shapes(c6, tokens, batch))

    out["mellum.step"] = key(sm.swa_decode_batch_step_jit.lower(
        p6, z((B,), i32), z((B, 6), i32), np.int32(B), mellum(rows=N),
        (z((B, MP), i32), z((B, MP), i32)), mellum(batch=B), c6))
    out["mellum.page"] = key(sm.swa_decode_page_jit.lower(
        p6, z((1, P), i32), z((3,), i32), mellum(tokens=2 * P), mellum(), c6))
# The gated short-convolution family (PR 40; a tree from before lacks it).
try:
    from oncilla_tpu.models import conv_moe as cm
except ImportError:
    cm = None
if cm is not None:
    c5 = cm.ConvMoeConfig.tiny()
    p5 = cm.init_params(jax.random.key(0), c5)
    fam = cm.PAGED_FAMILY

    def conv(rows=None, batch=1, tokens=P):
        return tuple(z((rows, s[0]) + s[2:]) if rows else z(s)
                     for s in fam.leaf_shapes(c5, tokens, batch))

    def conv_carry(b):
        (shape, dt), = fam.carry_leaves(c5, b)
        return z(shape, dt)

    out["conv.step"] = key(cm.conv_decode_batch_step_jit.lower(
        p5, z((B,), i32), z((B, 4), i32), np.int32(B), conv(rows=N),
        z((B, MP), i32), conv(batch=B), conv_carry(B), c5))
    out["conv.page"] = key(cm.conv_decode_page_jit.lower(
        p5, z((1, P), i32), z((2,), i32), conv(tokens=2 * P), conv(),
        conv_carry(1), c5))
# The window family's page program over a chunk of several pages, three of
# them real (a tree from before takes one page a program).
K = getattr(sm.PAGED_FAMILY, "chunk_pages", 1)
if K > 1:
    out["swa.chunk"] = key(sm.swa_decode_page_jit.lower(
        p4, z((1, K * P), i32), z((3,), i32), swa(tokens=2 * P), swa(), c4,
        np.int32(3)))
    out["mellum.chunk"] = key(sm.swa_decode_page_jit.lower(
        p6, z((1, K * P), i32), z((3,), i32), mellum(tokens=2 * P), mellum(),
        c6, np.int32(3)))
print(json.dumps(out, indent=1))
