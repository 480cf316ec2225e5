"""The ``latent_moe_hc`` family through the benchmark (CPU, tiny size): a
whole tiny cell through ``run_cell`` with the COMMITTED adapter, reference,
bytes model and warmer (no dummies), its three readers on a synthetic trace,
the bytes model against the program's own parameter list, the control one
precision lower, and the family's census. A CPU run proves counts and
control flow, never a time or a rate."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "xing4.0-29b-a4b-d6.decode-heavy"
NEW_METRICS = ("moe.experts_touched_share", "moe.step_roofline_share",
               "prefill.page_roofline_share")


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def tiny_config() -> dict:
    """The tiny ``LatentMoeConfig`` as a configuration file."""
    from oncilla_tpu.models import LatentMoeConfig

    d = LatentMoeConfig.tiny().to_published()
    d.update({
        "name": "tiny-latent", "source": "tests", "family": "latent_moe_hc",
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "num_nextn_predict_layers": 0,
        "reduced": [], "assumed": {}, "guarantees": {"cold_replicas": 2},
        "tolerance": {"max_abs_dlogit": 1e-3, "argmax_share": 1.0,
                      "why": "float32 on the CPU: the paged path and the "
                             "plain forward differ by summation order alone"},
        "tolerance_served": {"max_logit_gap": 1e-3, "why": "as tolerance"},
    })
    return d


TINY_TRAFFIC = {
    "generator": "lognormal_turns",
    "why": "4 callers, everything HOT, at the tiny size", "who": "tests",
    "params": {"clients": 4, "arrivals": {"kind": "closed"},
               "shared_prefix_tokens": 0,
               "prompt": {"median": 20, "sigma": 0.5, "min": 9, "max": 38},
               "new_tokens": {"median": 9, "sigma": 0.4, "min": 5, "max": 14},
               "avoid_multiple_of": 8, "pool": 12, "shape_seed": 1},
    "engine": {"page_tokens": 8, "max_active": 4, "max_batch": 4,
               "prefix_cache": False, "prefetch_workers": 2, "hot_pages": 64,
               "warm_pages": 2, "cold_pages": 64, "cold_daemons": 3},
    "warm": {"warmer": "paged_latent_moe", "prefill_context_pages": 4,
             "fused_buckets": [[4, 4, 16]], "pool_rows": [16, 32],
             "ramp": [[1, 1], [2, 1]], "requests": 6},
    "expect": {"window_promotes_max": 0},
}


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_latent")


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration of the committed
    family and a tiny mix added; the family's files are the committed ones."""
    tmp = tmp_path_factory.mktemp("bench_latent")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (tmp / "benchmark/configs/tiny-latent.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "benchmark/traffic/tiny-decode.json").write_text(
        json.dumps(TINY_TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-latent", "source": "tests",
                         "reduced": [], "why": "tests",
                         "file": "benchmark/configs/tiny-latent.json"})
    b["workloads"].append({"name": "tiny-latent.tiny-decode",
                           "config": "tiny-latent", "traffic": "tiny-decode",
                           "chips": 1, "why": "tests"})
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-latent.tiny-decode")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


def test_the_cell_and_its_three_metrics_are_entries_of_their_own():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == b["workloads"][-1] and cell["chips"] == 1
    assert cell["traffic"] == "decode-heavy"
    conf = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert conf == b["configs"][-1]
    assert [m["name"] for m in b["per_layer"][-3:]] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] for m in b["per_layer"][-3:])
    with open(os.path.join(ROOT, conf["file"])) as f:
        file = json.load(f)
    assert file["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    assert file["published"] == {"num_hidden_layers": 40,
                                 "first_k_dense_replace": 2,
                                 "num_nextn_predict_layers": 1}
    # every width as published
    for key, want in (("hidden_size", 3584), ("intermediate_size", 9216),
                      ("moe_intermediate_size", 1024), ("q_lora_rank", 768),
                      ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
                      ("qk_rope_head_dim", 64), ("v_head_dim", 128),
                      ("n_routed_experts", 64), ("num_experts_per_tok", 4),
                      ("vocab_size", 131072), ("hc_mult", 4)):
        assert file[key] == want, key


def test_bytes_model_counts_the_programs_own_parameters(harness):
    """The bytes model is shapes alone and imports nothing of the program;
    here it is held to the program's parameter list, leaf by leaf."""
    import math

    from oncilla_tpu.models import LatentMoeConfig

    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    bm = family.bytes_model
    cfg = family.adapter.program_config(cell.config)
    assert isinstance(cfg, LatentMoeConfig) and cfg.num_hidden_layers == 6
    spec = sys.modules[LatentMoeConfig.__module__].param_spec(cfg)
    size = {k: math.prod(shape) * (4 if dt == "float32" else 2)
            for k, (shape, _, dt) in spec.items()}
    routed = sum(size[k] for k in ("w_gate_e", "w_up_e", "w_down_e"))
    assert bm.weight_bytes(cell.config) == sum(size.values())
    assert bm.fixed_weight_bytes(cell.config) == (
        sum(size.values()) - routed - size["embed"])
    assert bm.expert_bytes(cell.config) * 64 * 5 == routed
    assert 9.5e9 < bm.weight_bytes(cell.config) < 9.7e9
    # a 16-token page: one latent leaf of 576 values a position a layer
    from oncilla_tpu.serving.engine import ServingEngine

    assert (bm.page_bytes(cell.config, 16) == 6 * 16 * 576 * 4
            == ServingEngine.page_nbytes(cfg, 16))
    # the least a step moves is never more than what any routing counts
    least = bm.decode_step_bytes(cell.config, 4000)
    assert least == bm.step_bytes_counted(cell.config, 4000, 5 * 4)
    assert least < bm.step_bytes_counted(cell.config, 4000, 5 * 41)
    assert (bm.step_bytes_counted(cell.config, 4000, 21) - least
            == bm.expert_bytes(cell.config))


def test_the_three_readers_on_a_synthetic_trace(harness):
    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    bm = family.bytes_model
    tr = harness.load_plugin("", "trace_reduce")
    trace = {"programs": {
        "jit_latent_decode_batch_step_jit": {"count": 50, "total_s": 0.75},
        "jit_latent_decode_page_jit": {"count": 4, "total_s": 0.08},
        "jit_paged_decode_batch_step_jit": {"count": 9, "total_s": 9.0}}}
    info = {"config": cell.config, "window": {"context_tokens": 400000},
            "peak": {"hbm_bytes_per_s": 819e9},
            "lib": {"trace_reduce": tr, "family": family.adapter,
                    "bytes_model": bm}}
    stats = {"batch": {"steps": 100},
             "moe": {"step_expert_rows": 100 * 200, "step_assignments": 32000,
                     "page_expert_rows": 10 * 180, "page_count": 10}}

    def read(name, stats=stats, trace=trace):
        return harness.load_plugin("layer_metrics", name).read(
            stats, {}, trace, info)

    assert read("moe.experts_touched_share") == pytest.approx(
        100 * 200 / (64 * 5))
    step_s, page_s = 0.75 / 50, 0.08 / 4
    assert read("moe.step_roofline_share") == pytest.approx(
        100 * bm.step_bytes_counted(cell.config, 4000, 200) / 819e9 / step_s)
    assert read("prefill.page_roofline_share") == pytest.approx(
        100 * bm.page_bytes_counted(cell.config, 0, 180) / 819e9 / page_s)
    assert 0 < read("step.roofline_share") < read("moe.step_roofline_share") < 100
    assert read("step.device_ms") == pytest.approx(15.0)
    # a program without the counters (the parent, a dense family), no trace,
    # or no such program in it: nothing is reported and nothing raises
    bare = {"batch": {"steps": 100}}
    for name in NEW_METRICS:
        assert read(name, stats=bare) is None
    assert read("moe.experts_touched_share", trace=None) is not None
    assert read("moe.step_roofline_share", trace=None) is None
    assert read("prefill.page_roofline_share", trace=None) is None
    assert read("moe.step_roofline_share", trace={"programs": {}}) is None
    assert read("prefill.page_roofline_share", trace={"programs": {}}) is None
    dense = harness.load_family(
        harness.load_cell("internlm2-1.8b.agent-shared").config)
    info["lib"].update(family=dense.adapter, bytes_model=dense.bytes_model)
    assert read("moe.step_roofline_share") is None
    assert read("prefill.page_roofline_share") is None


def test_a_tiny_cell_of_the_family_runs_whole_and_is_correct(tiny_copy):
    """All of ``run_cell`` but its look for a chip, on the committed
    adapter, reference, bytes model and warmer."""
    import jax

    from oncilla_tpu.serving.metrics import ServingStats

    h = load(str(tiny_copy / "benchmark/harness.py"), "bench_harness_latent_copy")
    cell = h.load_cell("tiny-latent.tiny-decode")
    family = h.load_family(cell.config)
    for mod, rel in ((family.adapter, "families/latent_moe_hc.py"),
                     (family.reference, "references/latent_moe_hc.py"),
                     (family.bytes_model, "bytes_models/latent_moe_hc.py")):
        assert mod.__file__ == str(tiny_copy / "benchmark" / rel)
    assert [m["name"] for m in cell.per_layer][-3:] == list(NEW_METRICS)
    counted = []
    note = ServingStats.note_moe_step

    def spy(self, rows, assignments):
        counted.append((rows, assignments))
        return note(self, rows, assignments)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    ServingStats.note_moe_step = spy
    try:
        line = h.run_cell("tiny-latent.tiny-decode", seed=2**31 + 29,
                          seconds=2.0, trace=False,
                          t_start=time.perf_counter(), platform="cpu")
    finally:
        ServingStats.note_moe_step = note
        for k, v in saved.items():
            jax.config.update(k, v)
    line = json.loads(json.dumps(line))
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_s", "ttft_ms_p90", "itl_ms_p95",
                                    "setup_s"}
    c = line["compared"]
    assert c["max_abs_dlogit"]["value"] <= 1e-3
    assert c["argmax_share"]["value"] == 1.0
    assert c["served_tokens"]["value"] > 0 and c["window_promotes"]["value"] == 0
    # every fused step handed its count back: k experts a row a layer at
    # least once, never more pairs than assignments
    assert counted and all(4 <= rows <= a for rows, a in counted)
    assert {a for _, a in counted} <= {4 * n for n in range(1, 5)}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_one_precision_lower_is_not_correct(seed):
    control = load(os.path.join(BENCH, "control.py"), "bench_control_latent")
    out = control.control(tiny_config(), seed, tokens=40)
    assert out["lower"] == "bfloat16" and out["correct"] is False
    d = out["max_abs_dlogit"]
    assert d["value"] > 3 * d["limit"]
    control.LOWER["float32"] = ("float32", 8, 23)
    try:
        same = control.control(tiny_config(), seed, tokens=40)
    finally:
        control.LOWER["float32"] = ("bfloat16", 8, 7)
    assert same["correct"] is True and same["max_abs_dlogit"]["value"] == 0


def test_census_finds_only_shapes_the_mix_warms():
    census = load(os.path.join(BENCH, "census_latent_moe.py"),
                  "bench_census_latent")
    out = census.census("decode-heavy", [3], requests=4)
    with open(os.path.join(BENCH, "traffic", "decode-heavy.json")) as f:
        warm = json.load(f)["warm"]
    assert out["fused_buckets"] and out["by_seed"][3]["ticks"] > 0
    assert (max(c for c, _ in out["prefill_context_pages"])
            < warm["prefill_context_pages"])
    warmed = {tuple(b) for b in warm["fused_buckets"]}
    # A full house: batch 16. (Its first steps when sixteen sessions start
    # together, which the harness's ramp never offers, have fewer rows than
    # any bucket warmed; smaller batches are the census's own drain.)
    full = {tuple(b) for b, _ in out["fused_buckets"]
            if b[0] == 16 and b[2] >= 64}
    assert full and full <= warmed
    assert {b[2] for b in warmed} <= set(warm["pool_rows"])
    assert out["by_seed"][3]["moe"]["step_expert_rows"] > 0
    assert np.all([b[0] <= 16 and b[1] <= 32 and b[2] <= 512 for b in warmed])
