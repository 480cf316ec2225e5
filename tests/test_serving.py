"""serving/ — tiered page store, prefix sharing, engine, fetch_pages(out=).

CPU-only (conftest pins the backend). The engine tests use the tiny
llama config so jit compiles stay in CI budget; cluster-backed legs
(remote cold tier, chaos) live in ``python -m oncilla_tpu.serving
--smoke`` (scripts/check.sh) — here the cold tier runs in its local
stand-in (``cold_sim``) unless a test spins its own cluster.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.core.errors import OcmInvalidHandle
from oncilla_tpu.serving.metrics import ServingStats, colocated, publish, unpublish
from oncilla_tpu.serving.prefix import PrefixCache
from oncilla_tpu.serving.tiers import TIER_PRIORITY, Tier, TieredPageStore

PB = 4096


def make_store(hot=2, warm=3, **kw):
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=1 << 20,
    ))
    store = TieredPageStore(ctx, PB, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("test"), **kw)
    return ctx, store


def page_data(seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, PB, dtype=np.uint8
    )


# -- tiers -------------------------------------------------------------------


def test_alloc_prefers_hot_and_demotes_lru():
    ctx, store = make_store(hot=2, warm=2)
    datas = [page_data(i) for i in range(5)]
    pages = [store.alloc_page(d) for d in datas]
    occ = store.occupancy()
    # Bounded tiers hold at most their capacity; the overflow went cold.
    assert occ["hbm"]["pages"] <= 2
    assert occ["host"]["pages"] <= 2
    assert occ["remote"]["pages"] >= 1
    # The NEWEST page is hot (LRU demotion victimized the oldest).
    assert pages[-1].tier == Tier.HOT
    assert pages[0].tier in (Tier.WARM, Tier.COLD)
    # Byte-exact through every tier.
    for p, d in zip(pages, datas):
        assert bytes(store.read_page(p)) == d.tobytes(), p.tier
    store.close()
    ctx.tini()


def test_promote_and_demote_roundtrip_byte_exact():
    ctx, store = make_store(hot=2, warm=2)
    d = page_data(7)
    p = store.alloc_page(d)
    store.demote(p, Tier.COLD)
    assert p.tier == Tier.COLD
    assert store.stats.demotes >= 1
    store.promote(p)
    assert p.tier == Tier.HOT
    assert store.stats.promotes >= 1
    assert bytes(store.read_page(p)) == d.tobytes()
    store.close()
    ctx.tini()


def test_stale_prefetched_bytes_discarded_on_version_mismatch():
    ctx, store = make_store(hot=2, warm=2)
    d1, d2 = page_data(1), page_data(2)
    p = store.alloc_page(d1)
    store.demote(p, Tier.COLD)
    buf = np.empty(PB, np.uint8)
    version, ok = store.fetch_bytes(p, buf)
    assert ok and bytes(buf) == d1.tobytes()
    store.write_page(p, d2)  # rewrite AFTER the fetch
    store.promote(p, data=buf, version=version)  # stale: must re-read
    assert bytes(store.read_page(p)) == d2.tobytes()
    store.close()
    ctx.tini()


def test_shared_referenced_page_never_victimized():
    ctx, store = make_store(hot=2, warm=2)
    shared = store.alloc_page(page_data(0), shared=True)
    shared.refs += 1
    # Flood the store: demotion pressure everywhere.
    others = [store.alloc_page(page_data(i + 1)) for i in range(6)]
    assert shared.tier == Tier.HOT, (
        "a referenced shared hot extent was victimized"
    )
    # Immutable while referenced.
    with pytest.raises(OcmInvalidHandle):
        store.write_page(shared, page_data(9))
    with pytest.raises(OcmInvalidHandle):
        store.free_page(shared)
    # Released, it becomes an ordinary (old, LRU-first) victim.
    shared.refs -= 1
    store.alloc_page(page_data(50))
    store.alloc_page(page_data(51))
    assert shared.tier != Tier.HOT
    for p in others:
        assert not p.freed
    store.close()
    ctx.tini()


def test_pinned_page_never_demoted():
    ctx, store = make_store(hot=1, warm=2)
    p = store.alloc_page(page_data(0))
    store.pin(p)
    store.alloc_page(page_data(1))
    assert p.tier == Tier.HOT
    store.unpin(p)
    store.close()
    ctx.tini()


def test_cow_private_copy_original_byte_exact():
    ctx, store = make_store()
    d = page_data(3)
    shared = store.alloc_page(d, shared=True)
    shared.refs += 1
    clone = store.cow(shared)
    assert clone.page_id != shared.page_id
    assert not clone.shared
    store.write_page(clone, page_data(4))
    assert bytes(store.read_page(shared)) == d.tobytes()
    assert store.stats.cow_copies == 1
    store.close()
    ctx.tini()


def test_tier_priority_mapping_is_the_qos_ladder():
    from oncilla_tpu.qos.policy import PRIO_HIGH, PRIO_LOW, PRIO_NORMAL

    assert TIER_PRIORITY[Tier.HOT] == PRIO_HIGH
    assert TIER_PRIORITY[Tier.WARM] == PRIO_NORMAL
    assert TIER_PRIORITY[Tier.COLD] == PRIO_LOW


# -- prefix cache ------------------------------------------------------------


def test_prefix_publish_match_and_dedup():
    ctx, store = make_store(hot=8, warm=8)
    cache = PrefixCache(store, page_tokens=4)
    toks = (1, 2, 3, 4)
    p1 = store.alloc_page(page_data(0))
    ext = cache.publish(None, toks, p1)
    assert ext.page is p1 and p1.shared
    # Content-hash dedup: a second tenant's identical page folds in.
    p2 = store.alloc_page(page_data(0))
    ext2 = cache.publish(None, toks, p2)
    assert ext2 is ext
    assert p2.freed
    matched, n = cache.match((1, 2, 3, 4, 9, 9))
    assert matched == [ext] and n == 4
    assert cache.child(None, toks) is ext
    assert cache.child(ext, toks) is None
    store.close()
    ctx.tini()


def test_prefix_partial_and_chain_match():
    ctx, store = make_store(hot=8, warm=8)
    cache = PrefixCache(store, page_tokens=4)
    full = cache.publish(None, (1, 2, 3, 4), store.alloc_page(page_data(0)))
    part = cache.publish(full, (5, 6), store.alloc_page(page_data(1)))
    matched, n = cache.match((1, 2, 3, 4, 5, 6))
    assert matched == [full, part] and n == 6
    # Divergent tail: only the full page matches.
    matched, n = cache.match((1, 2, 3, 4, 5, 7))
    assert matched == [full] and n == 4
    store.close()
    ctx.tini()


def test_prefix_refcount_churn_and_sweep():
    """Two tenants share a chain; one releases — refcounts drop, the
    shared extents survive byte-exact; sweep only reclaims unreferenced
    LEAVES (an inner node backing a referenced chain stays)."""
    ctx, store = make_store(hot=8, warm=8)
    cache = PrefixCache(store, page_tokens=4)
    d0, d1 = page_data(0), page_data(1)
    root = cache.publish(None, (1, 2, 3, 4), store.alloc_page(d0))
    leaf = cache.publish(root, (5, 6, 7, 8), store.alloc_page(d1))
    for e in (root, leaf):
        cache.acquire(e)   # tenant A
        cache.acquire(e)   # tenant B
    assert root.refs == 2 and leaf.refs == 2
    for e in (root, leaf):
        cache.release(e)   # tenant A leaves
    assert root.refs == 1 and leaf.refs == 1
    assert bytes(store.read_page(root.page)) == d0.tobytes()
    assert bytes(store.read_page(leaf.page)) == d1.tobytes()
    # Unreferenced leaf of a still-referenced chain: nothing sweepable
    # until the last tenant leaves.
    assert cache.sweep() == 0
    for e in (root, leaf):
        cache.release(e)
    assert cache.sweep() == 2
    assert root.page.freed and leaf.page.freed
    assert cache.match((1, 2, 3, 4)) == ([], 0)
    store.close()
    ctx.tini()


def test_prefix_shared_bytes_counts_dedup():
    ctx, store = make_store(hot=8, warm=8)
    cache = PrefixCache(store, page_tokens=4)
    ext = cache.publish(None, (1, 2, 3, 4), store.alloc_page(page_data(0)))
    assert cache.shared_bytes() == 0
    cache.acquire(ext)
    cache.acquire(ext)
    assert cache.shared_bytes() == PB  # one tenant's copy deduplicated
    store.close()
    ctx.tini()


# -- engine ------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from oncilla_tpu.models import LlamaConfig, init_params_host

    cfg = LlamaConfig.tiny()
    return cfg, init_params_host(0, cfg)


def run_engine(tiny_model, share: bool, prompts, new_tokens=6,
               hot=3, warm=4, prefetch=0):
    from oncilla_tpu.serving.engine import Request, ServingEngine

    cfg, params = tiny_model
    pb = ServingEngine.page_nbytes(cfg, 8)
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=1 << 20,
    ))
    store = TieredPageStore(ctx, pb, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats("t"))
    prefix = PrefixCache(store, 8) if share else None
    eng = ServingEngine(params, cfg, store, prefix, page_tokens=8,
                        max_active=4, prefetch_workers=prefetch, name="t")
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=p,
                               max_new_tokens=new_tokens))
        results = eng.run()
        outs = {r.tenant: list(r.out_tokens) for r in results}
        meta = eng.metrics_meta()
        reused = {r.tenant: r.prefix_tokens_reused for r in results}
    finally:
        eng.close()
        store.close()
        ctx.tini()
    return outs, meta, reused


@pytest.fixture(scope="module")
def shared_prompts(tiny_model):
    cfg, _ = tiny_model
    rng = np.random.default_rng(3)
    shared = rng.integers(1, cfg.vocab, 20).tolist()
    p0 = shared + rng.integers(1, cfg.vocab, 4).tolist()
    return [p0, list(p0), shared + rng.integers(1, cfg.vocab, 3).tolist()]


def test_engine_sharing_is_output_invariant(tiny_model, shared_prompts):
    outs_ns, meta_ns, _ = run_engine(tiny_model, False, shared_prompts)
    outs_sh, meta_sh, reused = run_engine(tiny_model, True, shared_prompts)
    # Sharing is a storage optimization: outputs byte-identical.
    assert outs_sh == outs_ns
    # Identical prompts -> identical outputs.
    assert outs_sh["t0"] == outs_sh["t1"]
    # The sharing machinery actually engaged.
    assert meta_sh["prefix"]["hits"] > 0
    assert meta_sh["prefix"]["cow"] >= 1          # the t0/t1 pair
    assert reused["t1"] > 0 and reused["t2"] > 0  # cross-tenant reuse
    assert meta_ns["prefix"]["hits"] == 0
    # Every decode produced the requested tokens.
    assert all(len(v) == 6 for v in outs_sh.values())


def test_engine_deterministic_across_runs(tiny_model, shared_prompts):
    outs1, _, _ = run_engine(tiny_model, True, shared_prompts)
    outs2, _, _ = run_engine(tiny_model, True, shared_prompts)
    assert outs1 == outs2


def test_engine_threaded_prefetch_matches(tiny_model, shared_prompts):
    outs0, _, _ = run_engine(tiny_model, True, shared_prompts)
    outs2, meta2, _ = run_engine(tiny_model, True, shared_prompts,
                                 prefetch=2)
    assert outs0 == outs2
    assert meta2["prefetch"]["mode"] == "thread"


# -- metrics / obs -----------------------------------------------------------


def test_serving_prom_families_validate(tiny_model, shared_prompts):
    from oncilla_tpu.obs import prom

    _, meta, _ = run_engine(tiny_model, True, shared_prompts)
    text = prom.render_serving({"engines": [meta]}, rank=0)
    fams = prom.validate(text)
    for fam in ("ocm_serving_tokens_total", "ocm_kv_hit_ratio",
                "ocm_kv_tier_bytes", "ocm_prefix_shared_bytes",
                "ocm_prefix_hits_total", "ocm_prefix_cow_total",
                "ocm_prefetch_stall_seconds_total",
                "ocm_kv_page_moves_total",
                "ocm_serving_batch_size", "ocm_serving_step_seconds",
                "ocm_serving_prefill_chunks_total"):
        assert fam in fams, fam
    # And through the daemon-side render() path (colocated meta).
    full = prom.render({"rank": 0, "serving": {"engines": [meta]}})
    assert "ocm_kv_hit_ratio" in prom.validate(full)


def test_colocated_publication_registry():
    st = ServingStats("pub-test")
    st.note_tokens(3)
    assert colocated() is None or all(
        e["engine"] != "pub-test" for e in colocated()["engines"]
    )
    publish(st)
    try:
        metas = colocated()["engines"]
        assert any(e["engine"] == "pub-test"
                   and e["tokens"]["decode"] == 3 for e in metas)
    finally:
        unpublish(st)
    got = colocated()
    assert got is None or all(
        e["engine"] != "pub-test" for e in got["engines"]
    )


def test_obs_table_serving_rows():
    from oncilla_tpu.obs.__main__ import _serving_rows

    st = ServingStats("rowtest")
    st.note_tokens(5, phase="prefill")
    st.note_tokens(7)
    st.note_lookup(True)
    st.set_occupancy({"hbm": 1, "host": 2, "remote": 3},
                     {"hbm": PB, "host": 2 * PB, "remote": 3 * PB})
    st.note_batch_step(3, 0.002)
    st.note_batch_step(1, 0.001)
    rows = _serving_rows(1, {"serving": {"engines": [st.snapshot()]}})
    assert rows == [["rowtest", "1", "5/7", "100%", "0.0", "1/2/3",
                     "0B", "0/0", "2.0/3"]]
    assert _serving_rows(0, {}) == []


# -- PagedKVCache fetch_pages(out=) regression -------------------------------


class _RecordingBackend:
    """Host-kind backend double: stores bytes, exposes get_into (the
    PR-3 registered-receive API), and records every destination buffer
    so the test can pin reuse."""

    def __init__(self):
        self.blobs: dict[int, np.ndarray] = {}
        self.next_id = 1
        self.get_into_calls = 0
        self.plain_gets = 0
        self.dest_bases: list[int] = []

    def alloc(self, nbytes, kind):
        from oncilla_tpu.core.arena import Extent
        from oncilla_tpu.core.handle import OcmAlloc
        from oncilla_tpu.core.kinds import Fabric, OcmKind

        aid = self.next_id
        self.next_id += 1
        self.blobs[aid] = np.zeros(nbytes, np.uint8)
        return OcmAlloc(alloc_id=aid, kind=OcmKind.REMOTE_HOST,
                        fabric=Fabric.DCN, nbytes=nbytes, rank=0,
                        device_index=0, extent=Extent(0, nbytes),
                        origin_rank=0)

    def free(self, handle):
        del self.blobs[handle.alloc_id]

    def put(self, handle, data, offset):
        raw = np.ascontiguousarray(np.asarray(data)).view(np.uint8).reshape(-1)
        self.blobs[handle.alloc_id][offset:offset + raw.nbytes] = raw

    def get(self, handle, nbytes, offset=0):
        self.plain_gets += 1
        return self.blobs[handle.alloc_id][offset:offset + nbytes].copy()

    def get_into(self, handle, out, offset=0):
        self.get_into_calls += 1
        base = out.__array_interface__["data"][0]
        self.dest_bases.append(base)
        out[:] = self.blobs[handle.alloc_id][offset:offset + out.nbytes]
        return out


def test_fetch_pages_reuses_registered_buffer(tiny_model):
    import jax.numpy as jnp

    from oncilla_tpu.core.kinds import OcmKind
    from oncilla_tpu.models import PagedKVCache

    cfg, _ = tiny_model
    backend = _RecordingBackend()
    cache = PagedKVCache(backend, cfg, batch=1, page_tokens=4,
                         kind=OcmKind.REMOTE_HOST, dtype="float32")
    rng = np.random.default_rng(0)
    shape = (cfg.n_layers, 1, cfg.n_kv_heads, 4, cfg.head_dim)
    kpages = [jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
              for _ in range(2)]
    vpages = [jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
              for _ in range(2)]
    for k, v in zip(kpages, vpages):
        cache.store_page(k, v)

    ks, vs = cache.fetch_pages()
    # The remote tier rode the registered-receive path, one distinct
    # slot per page, never a fresh allocation per fetch.
    assert backend.get_into_calls == 2
    assert backend.plain_gets == 0
    assert len(set(backend.dest_bases)) == 2
    buf1 = cache._recvbuf
    assert buf1 is not None

    ks2, vs2 = cache.fetch_pages()
    assert cache._recvbuf is buf1  # REUSED across fetches
    assert backend.get_into_calls == 4
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(ks2))
    # Byte-exact vs what was stored.
    np.testing.assert_allclose(
        np.asarray(ks), np.concatenate([np.asarray(k) for k in kpages],
                                       axis=3),
    )
    np.testing.assert_allclose(
        np.asarray(vs2), np.concatenate([np.asarray(v) for v in vpages],
                                        axis=3),
    )
    cache.free()


def test_models_package_exports():
    import oncilla_tpu.models as m

    for name in m.__all__:
        assert getattr(m, name) is not None
    with pytest.raises(AttributeError):
        m.not_a_symbol


# -- free ladder (runtime) ---------------------------------------------------


def test_free_ladder_survives_dead_primary():
    """A replicated handle whose primary was killed must still free:
    the client's free ladder re-aims at the promoted replica, which
    fans the DO_FREE out (was: UNKNOWN 'peer unreachable')."""
    import time

    from oncilla_tpu.core.kinds import OcmKind
    from oncilla_tpu.runtime.cluster import local_cluster
    from oncilla_tpu.utils.config import OcmConfig

    cfg = OcmConfig(
        host_arena_bytes=8 << 20, device_arena_bytes=1 << 20,
        heartbeat_s=0.05, lease_s=5.0, replicas=2,
        detect_interval_s=0.05, suspect_after=1, dead_after=2,
        probe_timeout_s=0.25, dcn_stripes=1, chunk_bytes=256 << 10,
    )
    with local_cluster(3, config=cfg) as cl:
        client = cl.client(0)
        h = client.alloc(1 << 20, OcmKind.REMOTE_HOST)
        assert h.replica_ranks
        client.put(h, np.arange(1 << 20, dtype=np.uint8), 0)
        owner = h.rank
        cl.kill(owner)
        # Free while the owner is dead; the ladder must land it on the
        # replica chain (retrying through the failover window).
        deadline = time.monotonic() + 15.0
        while True:
            try:
                client.free(h)
                break
            except Exception:  # noqa: BLE001 — detection window
                if time.monotonic() >= deadline:
                    raise
                h.freed = False
                time.sleep(0.2)
        for d in cl.daemons:
            if d.rank != owner:
                deadline = time.monotonic() + 10.0
                while (d.registry.live_count()
                       and time.monotonic() < deadline):
                    time.sleep(0.1)
                assert d.registry.live_count() == 0, d.rank


# -- chip_smoke.py: the serving path's on-chip proof, rehearsed on the CPU --

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP_SMOKE = os.path.join(_ROOT, "chip_smoke.py")


def _chip_smoke(tmp_path, *args):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, _CHIP_SMOKE, *args], env=env, cwd=_ROOT,
        capture_output=True, text=True, timeout=300,
    )


def _smoke_lines(stdout):
    """chip_smoke.py's standard output: the report, then as the last line
    the verdict the driver reads, which holds exactly ``ok`` and
    ``device`` (platform, kind, count)."""
    import json

    report, verdict = map(json.loads, stdout.strip().splitlines()[-2:])
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict == {"ok": report["ok"], "device": report["device"]}
    assert isinstance(verdict["ok"], bool)
    assert isinstance(verdict["device"]["count"], int)
    return report


def test_chip_smoke_refuses_cpu_and_rehearses(tmp_path):
    """Without a TPU chip_smoke.py exits non-zero before doing any work
    and prints no result; --cpu-rehearsal drives the same legs (memory
    plane, chip-to-chip, serving over a local_cluster COLD tier, the
    unpaged reference) at tiny size and says which platform it ran on."""
    refused = _chip_smoke(tmp_path)
    assert refused.returncode == 2, refused.stderr[-2000:]
    assert refused.stdout == ""
    assert "no CPU path" in refused.stderr

    run = _chip_smoke(tmp_path, "--cpu-rehearsal")
    assert run.returncode == 0, run.stderr[-4000:]
    line = _smoke_lines(run.stdout)
    assert line["ok"] is True and "failed" not in line
    assert line["device"]["platform"] == "cpu"
    assert line["sizing"] == "rehearsal"
    assert list(line["phases"]) == [
        "memory_plane", "weights", "multichip", "serving", "reference"
    ]
    serving = line["phases"]["serving"]
    assert serving["requests"] > serving["max_batch"]
    assert serving["prefix_hits"] > 0 and serving["cow"] > 0
    assert all(serving["hops"][h] > 0 for h in
               ("hbm>host", "host>remote", "remote>hbm", "host>hbm"))
    assert serving["degraded"]["capacity_free"] == 0
    assert line["phases"]["reference"]["argmax_share"] == 1.0


@pytest.mark.parametrize(
    "broken", ["memory_plane", "multichip", "weights", "serving",
               "reference", None],
)
def test_chip_smoke_fails_on_any_failed_phase(monkeypatch, capsys, tmp_path,
                                              broken):
    """A failure in any one phase stops the run, names the phase in the
    report line and makes the exit code non-zero (legs stubbed: this pins
    the driver, the rehearsal above runs the legs)."""
    import importlib

    import oncilla_tpu.models as models

    monkeypatch.syspath_prepend(_ROOT)
    cs = importlib.import_module("chip_smoke")

    def leg(name):
        def run(*args):
            if name == broken:
                raise AssertionError(f"injected into {name}")
            return []
        return run

    for name in ("memory_plane", "multichip", "serving", "reference"):
        monkeypatch.setattr(cs, name, leg(name))
    real_init = models.init_params_host
    monkeypatch.setattr(
        models, "init_params_host",
        lambda *a: leg("weights")() or real_init(*a),
    )
    # Set: the helper then touches no jax config in this test process.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = cs.main(["--cpu-rehearsal"])
    line = _smoke_lines(capsys.readouterr().out)
    if broken is None:
        assert rc == 0 and line["ok"] is True
    else:
        assert rc == 1 and line["ok"] is False
        assert line["failed"] == broken
        assert f"injected into {broken}" in line["error"]


_CORRUPT_ONE_COLD_PAGE = """
import sys
import numpy as np
import chip_smoke
from oncilla_tpu.serving.tiers import Tier, TieredPageStore

real_get, hit = TieredPageStore._get, []

def get(self, tier, handle, nbytes, out):
    raw = real_get(self, tier, handle, nbytes, out)
    if tier == Tier.COLD and not hit:
        hit.append(handle)
        raw = np.array(raw).reshape(-1, 4)[::-1].reshape(-1)
        if out is not None:
            out[:nbytes] = raw
    return raw

TieredPageStore._get = get
rc = chip_smoke.main(["--cpu-rehearsal"])
print("corrupted pages:", len(hit), file=sys.stderr)
sys.exit(rc)
"""


def test_chip_smoke_reference_catches_a_corrupted_page(tmp_path):
    """The reference phase is what notices a page that changed on a tier
    hop: permute the float32 words of the first page promoted back from
    COLD (finite values, wrong places) and the rehearsal fails there, on
    the logit tolerance. In a process of its own, like a real run."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "-c", _CORRUPT_ONE_COLD_PAGE], env=env, cwd=_ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert "corrupted pages: 1" in run.stderr, run.stderr[-2000:]
    line = _smoke_lines(run.stdout)
    assert run.returncode == 1 and line["ok"] is False
    assert line["failed"] == "reference", line.get("error")
    assert "max |dlogit|" in line["error"]


def test_compile_cache_dir_is_the_env_or_a_fixed_checkout_path(
        monkeypatch, tmp_path):
    """utils.platform.enable_compile_cache: with JAX_COMPILATION_CACHE_DIR
    set nothing is set in code (JAX reads the variable itself); unset, the
    cache is <checkout>/.jax_cache — the same string in two calls, in two
    processes and from two working directories, because the path is part
    of the cache key."""
    import subprocess
    import sys

    import jax

    from oncilla_tpu.utils import platform as plat

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, val: updates.append((key, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert plat.enable_compile_cache() == str(tmp_path)
    assert updates == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(_ROOT, ".jax_cache")
    assert plat.enable_compile_cache() == want == plat.enable_compile_cache()
    assert updates == [("jax_compilation_cache_dir", want)] * 2

    code = ("import jax; from oncilla_tpu.utils.platform import "
            "enable_compile_cache as e; print(e()); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = _ROOT
    seen = {
        subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                       capture_output=True, text=True, timeout=120).stdout
        for cwd in (_ROOT, str(tmp_path))
    }
    assert seen == {f"{want}\n{want}\n"}
