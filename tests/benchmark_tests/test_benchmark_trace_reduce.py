"""CPU tests of the benchmark's yardstick: the trace reduction on a trace
recorded on the chip, the bytes model against hand arithmetic, and the plain
reference against the program's own unpaged forward."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(name: str, path: str | None = None):
    spec = importlib.util.spec_from_file_location(
        "bench_%s_under_test" % name.replace("/", "_"),
        path or os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# -- trace_reduce ---------------------------------------------------------------


def test_union_counts_overlaps_once():
    tr = load("trace_reduce")
    assert tr.union_s([]) == 0
    assert tr.union_s([(0, 10), (5, 20), (30, 40), (32, 35)]) == pytest.approx(30e-9)
    assert tr.program_name("jit_step(123456789)") == "jit_step"
    assert tr.op_name("%fusion.123 = bf16[8,128] fusion(...)") == "fusion"
    assert tr.op_name("copy.4") == "copy"


def test_gaps_are_named_by_the_program_that_ends_them():
    tr = load("trace_reduce")
    mods = [("jit_a(1)", 0.0, 1e6), ("jit_b(2)", 3e6, 4e6), ("jit_a(1)", 4.00001e6, 5e6),
            ("jit_b(3)", 9e6, 10e6)]
    gaps = tr._gaps_before(mods)
    assert gaps == {"before jit_b": pytest.approx((2e6 + 4e6) / 1e9)}


FIXTURE = os.path.join(BENCH, "fixtures", "agent-shared.v5e.xplane.pb.gz")


def test_reduction_of_the_trace_recorded_on_the_chip():
    """A fraction of a second of ``internlm2-1.8b.agent-shared`` on a v5e,
    recorded by ``run.py --trace 1 --keep-trace`` in this PR."""
    tr = load("trace_reduce")
    red = tr.reduce(FIXTURE, chips=1)
    assert red["chips"] == 1
    assert red["busy_s"] > 0
    assert red["busy_s"] + red["idle_s"] == pytest.approx(red["span_s"])
    assert 0 < red["busy_s"] <= red["span_s"]
    # the fused step and the prefill program are found by name
    steps, step_s = tr.program(red, "paged_decode_batch_step")
    assert steps >= 1 and step_s > 0
    assert tr.program(red, "paged_decode_page")[0] >= 1
    assert tr.program(red, "no_such_program") == (0, 0)
    assert len(red["top_ops"]) <= 10 and red["top_ops"][0][1] > 0
    assert all(name.startswith("before ") for name, _ in red["idle_gaps"])
    # device time of programs cannot pass the busy time of their operations
    # by more than the gaps between operations inside a program
    assert sum(v["total_s"] for v in red["programs"].values()) <= red["span_s"] * 1.001
    with pytest.raises(ValueError):
        tr.reduce(FIXTURE, chips=0)


# -- bytes_model ----------------------------------------------------------------


def test_bytes_model_against_hand_arithmetic():
    bm = load("bytes_models/dense_gqa")
    shared = load("bytes_model")
    assert bm.DTYPE_BYTES is not None and bm.DTYPE_BYTES == shared.DTYPE_BYTES
    i = config("internlm2-1.8b")
    m = config("mistral-7b-v0.1-d16")
    # one 16-token page in the float32 store
    assert bm.page_bytes(i, 16) == 2 * 24 * 8 * 16 * 128 * 4 == 3 << 20
    assert bm.page_bytes(m, 16) == 2 * 16 * 8 * 16 * 128 * 4 == 2 << 20
    # InternLM2-1.8B: per layer q 2048x2048, k and v 2048x1024, o 2048x2048,
    # three FFN matrices 2048x8192; head 2048x92544; bf16
    per_layer = 2048 * 2048 * 2 + 2 * 2048 * 1024 + 3 * 2048 * 8192
    assert per_layer == 62_914_560
    want_i = (24 * per_layer + 2048 * 92544) * 2 + (24 * 2 * 2048 + 2048) * 4
    assert bm.weight_bytes(i) == want_i
    assert 3.39e9 < want_i < 3.41e9
    # with the embedding table the program holds 3.78 GB (the issue's 3.8 GB)
    assert want_i + bm.embedding_bytes(i) == pytest.approx(3.778e9, rel=1e-3)
    # Mistral-7B at 16 layers: q and o 4096x4096, k and v 4096x1024, FFN 4096x14336
    per_layer_m = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    want_m = (16 * per_layer_m + 4096 * 32000) * 2 + (16 * 2 * 4096 + 4096) * 4
    assert bm.weight_bytes(m) == want_m
    assert want_m + bm.embedding_bytes(m) == pytest.approx(7.504e9, rel=1e-3)
    # K and V of one position, bf16: 2 * layers * kv heads * 128 * 2 B
    assert bm.kv_bytes_per_token(i) == 2 * 24 * 8 * 128 * 2 == 98304
    assert bm.kv_bytes_per_token(m) == 65536
    assert bm.decode_step_bytes(i, 2400) == want_i + 2400 * 98304
    assert shared.page_copy_bytes(3 << 20) == 6 << 20
    # a step at 819 GB/s cannot take less than ~4.4 ms / ~9 ms
    assert bm.decode_step_bytes(i, 2400) / 819e9 == pytest.approx(4.44e-3, rel=0.01)
    assert bm.decode_step_bytes(m, 2400) / 819e9 == pytest.approx(9.03e-3, rel=0.01)


# -- reference ------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 5])
def test_reference_agrees_with_the_programs_unpaged_forward(window):
    import dataclasses

    import jax

    from oncilla_tpu.models import LlamaConfig, llama

    ref = load("references/dense_gqa")
    cfg = dataclasses.replace(LlamaConfig.tiny(), window=window)
    params = llama.init_params(jax.random.key(3), cfg)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab, (2, 23)).astype(np.int32)
    conf = {"num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "sliding_window": window}
    rows = np.arange(4, 23)
    got = ref.logits_at(params, tokens, rows, conf)
    want = np.asarray(llama.forward(params, tokens, cfg))[:, rows]
    assert got.shape == want.shape == (2, 19, cfg.vocab)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    if window is not None:
        # the window is really applied: the full-attention answer differs
        full = ref.logits_at(params, tokens, rows,
                             dict(conf, sliding_window=None))
        assert np.abs(full - got).max() > 1e-3


@pytest.mark.parametrize("window", [None, 5])
def test_the_moved_reference_is_bit_equal_to_the_one_it_was(window):
    """The yardstick did not move: ``references/dense_gqa.py`` against a
    copy of ``benchmark/reference.py`` as PR 27 left it, kept beside this
    test, on the tiny configuration."""
    import jax

    from oncilla_tpu.models import LlamaConfig, llama

    new = load("references/dense_gqa")
    old = load("reference_before_pr28",
               os.path.join(os.path.dirname(__file__), "reference_before_pr28.py"))
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(11), cfg)
    tokens = np.random.default_rng(1).integers(1, cfg.vocab, (2, 37)).astype(np.int32)
    conf = {"num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "sliding_window": window}
    rows = np.arange(3, 37)
    got = new.logits_at(params, tokens, rows, conf)
    want = old.logits_at(params, tokens, rows, old.dims_of(conf))
    assert got.dtype == want.dtype == np.float32
    assert np.array_equal(got, want)
    assert new.LAYER_LEAVES == old.LAYER_LEAVES
