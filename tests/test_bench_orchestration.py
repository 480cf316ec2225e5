"""Smoke-test bench.py's _run orchestration with the heavy stages stubbed.

The real stages are chip-gated, so a wiring bug in the stage graph (a
renamed key, a closure referencing a moved variable, bank_dcn semantics)
would otherwise surface only on the live chip — wasting chip budget or
the driver's end-of-round run. Here every expensive callable is
replaced with a cheap stand-in and the REAL _run drives the REAL banking
logic end to end; assertions pin the detail-block contract the grader
(oncilla_tpu/benchmarks/check.py) reads.
"""

import os
import sys
import time
import types

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    import bench as bench_mod

    # Tiny arena + copies so the ctx/alloc/put/get plumbing (which DOES
    # run for real, on CPU) stays fast.
    monkeypatch.setattr(bench_mod, "ARENA", 1 << 20)
    monkeypatch.setattr(bench_mod, "NBYTES", 128 << 10)
    monkeypatch.setattr(bench_mod, "ITERS", 2)

    # The stand-in "timed executables" must actually perform the stream
    # ping-pong (segment 2s -> 2s+1 per stream), because _run re-runs them
    # against stamped patterns and ZEROES any leg whose output is wrong —
    # a stub that doesn't copy is (correctly) discarded by the real
    # correctness machinery.
    def seg_copy(streams):
        def run(b):
            seg = bench_mod.NBYTES // streams
            for s in range(streams):
                src, dst = 2 * s * seg, 2 * s * seg + seg
                b = b.at[dst:dst + seg].set(b[src:src + seg])
            return b

        return run

    def fake_pallas_copy(buf, streams=2):
        bench_mod._LAST_RUN[("copy", streams)] = seg_copy(streams)
        return 500.0 + streams, buf

    def fake_remote(buf):
        bench_mod._LAST_RUN["remote"] = seg_copy(2)
        return 400.0, buf

    monkeypatch.setattr(bench_mod, "bench_pallas_copy", fake_pallas_copy)
    monkeypatch.setattr(bench_mod, "bench_pallas_remote", fake_remote)
    monkeypatch.setattr(bench_mod, "bench_xla_copy", lambda buf: (100.0, buf))
    monkeypatch.setattr(
        bench_mod, "check_pallas_ici_copy", lambda errors: True
    )
    monkeypatch.setattr(
        bench_mod, "check_dma_row_kernels", lambda errors: True
    )
    monkeypatch.setattr(
        bench_mod, "bench_gb_sweep",
        lambda errors, seconds=0: {"1073741824": [None, 6.0, 400.0]},
    )
    monkeypatch.setattr(
        bench_mod, "bench_dcn",
        lambda errors: {"put_gbps": 1.9, "get_gbps": 1.2, "verified": True},
    )
    monkeypatch.setattr(
        bench_mod, "bench_serving", lambda errors: {"tenants": 6},
    )

    # Stage modules imported inside _run: fake them BOTH in sys.modules
    # (for `from pkg.mod import name`) and as the package attribute (for
    # `from pkg import mod`, which resolves via getattr on the package).
    import oncilla_tpu.benchmarks as bpkg

    mfu_fake = types.SimpleNamespace(
        mfu_forward=lambda: {"mfu": 0.65, "tflops": 128.0},
        mfu_train_best=lambda deadline=None: {
            "mfu": 0.61, "tflops": 120.0, "variants": [{"mfu": 0.61}],
        },
    )
    monkeypatch.setitem(
        sys.modules, "oncilla_tpu.benchmarks.mfu", mfu_fake
    )
    monkeypatch.setattr(bpkg, "mfu", mfu_fake, raising=False)
    gups_fake = types.SimpleNamespace(
        gups_handle_best=lambda **kw: {"gups": 0.08, "mode": "handle:bincount"},
    )
    monkeypatch.setitem(
        sys.modules, "oncilla_tpu.benchmarks.gups", gups_fake
    )
    monkeypatch.setattr(bpkg, "gups", gups_fake, raising=False)
    ceiling_fake = types.SimpleNamespace(
        ceiling_probe=lambda deadline=None: {
            "read_only_gbps": 700.0,
            "copy_streams_gbps": {"2": 580.0},
            "vmem_roundtrip_gbps": 150.0,
        },
    )
    monkeypatch.setitem(
        sys.modules, "oncilla_tpu.benchmarks.ceiling", ceiling_fake
    )
    monkeypatch.setattr(bpkg, "ceiling", ceiling_fake, raising=False)
    kv_fake = types.SimpleNamespace(
        run_bench=lambda **kw: {
            "tok_s": {"plain": 500.0, "device_fused": 1700.0},
            "paging_overhead": {"device_fused": 0.48},
        },
    )
    monkeypatch.setitem(
        sys.modules, "oncilla_tpu.benchmarks.kv_decode", kv_fake
    )
    monkeypatch.setattr(bpkg, "kv_decode", kv_fake, raising=False)
    return bench_mod


def _drive(bench_mod, budget_s: float):
    out = {
        "metric": "m", "value": 0.0, "unit": "GB/s", "vs_baseline": 0.0,
        "detail": {"copy_nbytes": bench_mod.NBYTES,
                   "target_gbps": bench_mod.TARGET},
    }
    errors: dict = {}
    bench_mod._run(out, errors, deadline=time.monotonic() + budget_s)
    return out, errors


def test_full_budget_banks_every_stage(bench):
    out, errors = _drive(bench, budget_s=3600.0)
    d = out["detail"]
    # Headline from the stubbed copy loops.
    assert out["value"] > 0 and out["vs_baseline"] > 0
    # Every graded field landed.
    for key in ("ceiling", "gb_sweep", "dcn", "mfu", "mfu_train",
                "mfu_train_variants", "gups", "kv_decode_tok_s",
                "pallas_ici_verified", "dma_rows_verified"):
        assert key in d, (key, sorted(d), errors)
    assert d["dcn"]["verified"] is True
    # The grader passes on this doc end to end.
    from oncilla_tpu.benchmarks.check import grade

    verdicts = {name: v for name, v, _ in grade(out)}
    assert verdicts["ceiling probe banked (read_only + stream sweep)"] == "PASS"
    assert verdicts["GB-sweep read leg >= pallas_gbps / 2"] == "PASS"
    assert verdicts["mfu_train >= 0.60"] == "PASS"
    assert verdicts["dcn banked and verified"] == "PASS"


def test_truncated_budget_still_banks_cheap_graded_stages(bench):
    """The r5 reorder contract: with ~9 minutes left after the copy
    stages, ceiling + gb_sweep + the early DCN echo must bank even though
    the MFU stages would blow the budget (their budget gates skip them)."""
    out, errors = _drive(bench, budget_s=560.0)
    d = out["detail"]
    for key in ("ceiling", "gb_sweep", "dcn"):
        assert key in d, (key, sorted(d), errors)
    assert d["dcn"]["verified"] is True


def test_failed_tail_dcn_keeps_early_echo(bench, monkeypatch):
    """bank_dcn: an unverified tail re-run must not clobber a banked
    verified early echo."""
    import bench as bench_mod

    calls = [0]

    def flaky_dcn(errors):
        calls[0] += 1
        if calls[0] == 1:
            return {"put_gbps": 1.9, "get_gbps": 1.2, "verified": True}
        errors["dcn"] = "tail blew up"
        return {}

    monkeypatch.setattr(bench_mod, "bench_dcn", flaky_dcn)
    out, errors = _drive(bench, budget_s=3600.0)
    assert calls[0] == 2  # early echo + tail both ran
    assert out["detail"]["dcn"]["verified"] is True  # early echo survives


def test_main_refuses_without_a_tpu(bench, monkeypatch, capsys):
    """No CPU fallback and no child process: without a TPU, main() exits
    2 before any stage runs and prints no result line."""
    import subprocess

    def boom(*a, **kw):
        raise AssertionError("bench.main must not start a process")

    monkeypatch.setattr(subprocess, "run", boom)
    monkeypatch.setattr(subprocess, "Popen", boom)
    monkeypatch.setattr(
        bench, "_run",
        lambda *a: pytest.fail("a stage ran on a non-TPU backend"),
    )
    assert bench.main() == 2
    assert capsys.readouterr().out == ""


def test_main_exits_nonzero_on_a_failed_stage(bench, monkeypatch, capsys):
    import json

    import jax

    def failing_run(out, errors, deadline):
        out["value"] = 1.0
        errors["gups"] = "RuntimeError: boom"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        "oncilla_tpu.utils.platform.enable_compile_cache", lambda: "")
    monkeypatch.setattr(bench, "_run", failing_run)
    assert bench.main() == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["detail"]["errors"] == {"gups": "RuntimeError: boom"}
    assert line["detail"]["device"]["platform"] == "cpu"  # as JAX reports it
    monkeypatch.setattr(bench, "_run", lambda out, errors, deadline: None)
    assert bench.main() == 0
