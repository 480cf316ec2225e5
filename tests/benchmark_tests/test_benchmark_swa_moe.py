"""The ``swa_gqa_moe`` family and the cell of PR 36 through the benchmark
(CPU, tiny size): the entries found by NAME, the configuration held to the
catalog's numbers, the bytes model against the program's own parameter
list, the three readers on a synthetic trace, a whole tiny cell through
``run_cell`` with the COMMITTED adapter, reference, bytes model and warmer,
the control one precision lower, and the mix's sizes. A CPU run proves
counts and control flow, never a time or a rate."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "laguna-s-2.1-ep4-d5"
CELL = f"{CONFIG}.mixed-lengths"
NEW_METRICS = ("swa.step_roofline_share", "swa.page_roofline_share",
               "kv.held_share")
# Every number of the catalog entry's config (model-configs guide,
# architectures.jsonl, Laguna-S-2.1), but the three keys of the cut; the
# nested groups and the lists are held whole below.
PUBLISHED = {
    "model_type": "laguna", "hidden_size": 3072, "intermediate_size": 12288,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
}
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
}
LISTS = {
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3,
    "num_attention_heads_per_layer": [48, 72, 72, 72],
    "gating_types": ["per_head"] * 4,
}
CUT = {"num_hidden_layers": (48, 5), "num_experts": (256, 64),
       "vocab_size": (100352, 25088)}


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def config_file() -> dict:
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def traffic_file() -> dict:
    with open(os.path.join(BENCH, "traffic", "mixed-lengths.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    """A tiny configuration file of the family: every key the adapter holds
    at the value it holds it to, five layers F W W W F, a window of 20
    positions (2.5 pages of 8), 16 experts of which this chip holds 8."""
    from oncilla_tpu.models import SwaMoeConfig

    d = SwaMoeConfig.tiny(sliding_window=20, num_experts=8,
                          router_experts=16).to_published()
    d.update({
        "name": "tiny-swa", "source": "tests", "family": "swa_gqa_moe",
        "gating": "per-head", "norm_topk_prob": True,
        "reduced": [], "assumed": {}, "guarantees": {"cold_replicas": 2},
        "tolerance": {"max_abs_dlogit": 1e-3, "argmax_share": 1.0,
                      "why": "float32 on the CPU: the paged path and the "
                             "plain forward differ by summation order alone"},
        "tolerance_served": {"max_logit_gap": 1e-3, "why": "as tolerance"},
    })
    return d


TINY_TRAFFIC = {
    "generator": "lognormal_turns",
    "why": "4 callers, everything HOT, contexts past the tiny window",
    "who": "tests",
    "params": {"clients": 4, "arrivals": {"kind": "closed"},
               "shared_prefix_tokens": 0,
               "prompt": {"median": 30, "sigma": 0.6, "min": 9, "max": 70},
               "new_tokens": {"median": 9, "sigma": 0.4, "min": 5, "max": 14},
               "avoid_multiple_of": 8, "pool": 12, "shape_seed": 1},
    "engine": {"page_tokens": 8, "max_active": 4, "max_batch": 4,
               "prefix_cache": False, "prefetch_workers": 2, "hot_pages": 96,
               "warm_pages": 2, "cold_pages": 64, "cold_daemons": 3},
    "warm": {"warmer": "paged_swa_moe", "prefill_context_pages": 9,
             "fused_buckets": [[4, 512, 32, 3, 16]],
             "pool_rows": [[16, 32], [8, 16]],
             "ramp": [[1, 1], [2, 1]], "requests": 6},
    "expect": {"window_promotes_max": 0},
}


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_swa")


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration of the committed
    family and a tiny mix added; the family's files are the committed ones."""
    tmp = tmp_path_factory.mktemp("bench_swa")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (tmp / "benchmark/configs/tiny-swa.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "benchmark/traffic/tiny-mixed.json").write_text(
        json.dumps(TINY_TRAFFIC))
    b = bench_json()
    b["configs"].append({"name": "tiny-swa", "source": "tests",
                         "reduced": [], "why": "tests",
                         "file": "benchmark/configs/tiny-swa.json"})
    b["workloads"].append({"name": "tiny-swa.tiny-mixed",
                           "config": "tiny-swa", "traffic": "tiny-mixed",
                           "chips": 1, "why": "tests"})
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-swa.tiny-mixed")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


# -- the entries, by name ---------------------------------------------------------


def test_the_configuration_and_the_cell_are_entries_found_by_name():
    b = bench_json()
    conf = by_name(b["configs"], CONFIG)
    assert conf["reduced"] == list(CUT) == config_file()["reduced"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["source"] == config_file()["source"] and len(conf["why"]) <= 200
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed-lengths", 1)
    assert len(cell["why"]) <= 200 and "4x" in cell["why"]
    # nothing that was there moved: the accepted entries lead their lists
    assert [c["name"] for c in b["configs"]][:4] == [
        "internlm2-1.8b", "mistral-7b-v0.1-d16", "xing4.0-29b-a4b-d6",
        "ling-3.0-flash-vl-ep4-d7"]
    assert [w["name"] for w in b["workloads"]][:5] == [
        "internlm2-1.8b.agent-shared",
        "mistral-7b-v0.1-d16.sessions-overcommit",
        "xing4.0-29b-a4b-d6.decode-heavy",
        "ling-3.0-flash-vl-ep4-d7.state-decode",
        "mistral-7b-v0.1-d16.sessions-fit"]
    assert b["run_seconds"] == 45 and all(w["chips"] == 1
                                          for w in b["workloads"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_new_cell_alone(name):
    b = bench_json()
    m = by_name(b["per_layer"], name)
    assert m["workloads"] == [CELL] and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    layers = {e["layer"] for e in b["per_layer"]
              if e["name"].split(".")[0] == name.split(".")[0]}
    assert len(layers) == 1
    # no accepted metric's list of cells changed
    for old, cells in (("prefix.reused_share", 1), ("dma.roofline_share", 2),
                       ("moe.experts_touched_share", 1),
                       ("moe.step_roofline_share", 1),
                       ("prefill.page_roofline_share", 1),
                       ("kda.step_roofline_share", 1),
                       ("kda.page_roofline_share", 1),
                       ("moe.held_touched_share", 1),
                       ("carry.seats_kept_share", 1)):
        assert len(by_name(b["per_layer"], old)["workloads"]) == cells


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_as_the_catalog_has_it(key):
    assert config_file()[key] == PUBLISHED[key]


def test_the_nested_groups_and_the_lists_are_copied_whole():
    file = config_file()
    assert file["rope_parameters"] == ROPE
    for key, period in LISTS.items():
        assert file[key] == period * 12, key
    assert file["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert "torch_dtype" in file and file["torch_dtype"] == "bfloat16"


@pytest.mark.parametrize("key", sorted(CUT))
def test_a_cut_key_states_the_published_value_beside_its_own(key):
    file = config_file()
    published, here = CUT[key]
    assert file["published"][key] == published and file[key] == here
    assert key in file["reduced"]
    # never a width
    assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"


def test_the_cut_keeps_the_guides_floors_and_the_routers_width():
    file = config_file()
    assert file["router_experts"] == 256 and file["first_expert"] == 0
    assert file["num_experts_per_tok"] == 10
    kept = file["num_hidden_layers"]
    types = file["layer_types"][:kept]
    # the leading dense layer and one whole period after it
    assert file["mlp_layer_types"][:kept] == ["dense"] + ["sparse"] * 4
    assert sorted(types[1:]) == sorted(LISTS["layer_types"])
    assert kept - 1 >= 4 and file["num_experts"] >= 8
    assert file["vocab_size"] * 4 == file["published"]["vocab_size"]
    assert file["num_experts"] * 4 == file["published"]["num_experts"]
    for key in ("assumed", "deployment", "guarantees", "reduced_why"):
        assert file[key]
    for key in ("gate", "qk_norm", "rotary", "routing", "shared_expert",
                "cache", "store_dtype"):
        assert file["assumed"][key]
    assert "PROVISIONAL" not in json.dumps(file)
    for tol in ("tolerance", "tolerance_served"):
        assert len(file[tol]["why"]) > 200


@pytest.mark.parametrize("key,value", [
    ("gating", "per-layer"), ("norm_topk_prob", False),
    ("attention_bias", True), ("moe_router_logit_softcapping", 30),
    ("mlp_only_layers", [0, 1]), ("tie_word_embeddings", True)])
def test_the_adapter_raises_on_what_the_program_does_not_compute(
        harness, key, value):
    family = harness.load_family(config_file())
    family.adapter.program_config(config_file())
    with pytest.raises(ValueError, match=key):
        family.adapter.program_config({**config_file(), key: value})


def test_the_adapter_raises_on_another_gate_or_rotary_in_a_kept_layer(harness):
    family = harness.load_family(config_file())
    file = config_file()
    gates = list(file["gating_types"])
    gates[7] = "per_layer"      # a layer that is not kept: no matter
    family.adapter.program_config({**file, "gating_types": gates})
    gates[3] = "per_layer"
    with pytest.raises(ValueError, match="gating_types"):
        family.adapter.program_config({**file, "gating_types": gates})
    rope = json.loads(json.dumps(file["rope_parameters"]))
    rope["sliding_attention"]["rope_type"] = "yarn"
    with pytest.raises(ValueError, match="rope_type"):
        family.adapter.program_config({**file, "rope_parameters": rope})


# -- the bytes model ----------------------------------------------------------------


def test_bytes_model_counts_the_programs_own_parameters(harness):
    """The bytes model is shapes alone and imports nothing of the program;
    here it is held to the program's parameter list, leaf by leaf."""
    from oncilla_tpu.models import SwaMoeConfig
    from oncilla_tpu.serving.engine import ServingEngine

    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    bm = family.bytes_model
    cfg = family.adapter.program_config(cell.config)
    assert isinstance(cfg, SwaMoeConfig)
    assert cfg.full_layers == (0, 4) and cfg.window_layers == (1, 2, 3)
    assert cfg.num_attention_heads_per_layer == (48, 72, 72, 72, 48)
    assert cfg.experts_held == (0, 64) and cfg.n_routed_experts == 256
    assert cfg.first_k_dense_replace == 1 and cfg.sliding_window == 512
    module = sys.modules[SwaMoeConfig.__module__]
    spec = module.param_spec(cfg)
    size = {k: math.prod(shape) * (4 if dt == "float32" else 2)
            for k, (shape, _, dt) in spec.items()}
    routed = sum(size[k] for k in ("w_gate_e", "w_up_e", "w_down_e"))
    assert bm.weight_bytes(cell.config) == sum(size.values())
    assert bm.fixed_weight_bytes(cell.config) == (
        sum(size.values()) - routed - size["embed"])
    assert bm.expert_bytes(cell.config) * 64 * 4 == routed
    # the issue's count: 3.002 B parameters, 6.00 GB, 37.5 % of the chip
    assert 6.00e9 < bm.weight_bytes(cell.config) < 6.02e9
    params = sum(math.prod(shape) for shape, _, _ in spec.values())
    assert 3.001e9 < params < 3.003e9
    per_layer = {i: bm.attention_bytes(cell.config, i) // 2 for i in range(5)}
    assert per_layer[0] == per_layer[4] == 44187648     # 44.19 M
    assert per_layer[1] == per_layer[2] == per_layer[3] == 63135744     # 63.14 M
    # a 16-token page of each kind; the store is built for the larger
    pages = bm.page_bytes(cell.config, 16)
    assert pages == {"full": 256 << 10, "window": 384 << 10}
    assert ServingEngine.page_nbytes(cfg, 16) == max(pages.values())
    assert bm.layer_position_bytes(cell.config) == 2 * 8 * 128 * 2
    # the least a step moves: no held expert, one seat, one window
    least = bm.decode_step_bytes(cell.config, 4000)
    assert least == bm.step_bytes_counted(
        cell.config, 2 * 4000 + 3 * 512, 0, 1)
    assert bm.decode_step_bytes(cell.config, 100) == bm.step_bytes_counted(
        cell.config, 5 * 100, 0, 1)
    assert (bm.step_bytes_counted(cell.config, 0, 1, 1)
            - bm.step_bytes_counted(cell.config, 0, 0, 1)
            == bm.expert_bytes(cell.config))
    assert (bm.step_bytes_counted(cell.config, 10, 0, 1)
            - bm.step_bytes_counted(cell.config, 0, 0, 1)
            == 10 * 4096)
    assert (bm.step_bytes_counted(cell.config, 0, 0, 64)
            - bm.step_bytes_counted(cell.config, 0, 0, 1)
            == 63 * 2 * 5 * 16 * 4096)
    assert bm.page_bytes_counted(cell.config, 7, 9) == bm.step_bytes_counted(
        cell.config, 7, 9, 1)


# -- the readers ----------------------------------------------------------------------


@pytest.fixture()
def reading(harness):
    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    tr = harness.load_plugin("", "trace_reduce")
    trace = {"programs": {
        "jit_swa_decode_batch_step_jit": {"count": 50, "total_s": 1.0},
        "jit_swa_decode_page_jit": {"count": 4, "total_s": 0.04},
        "jit_kda_decode_batch_step_jit": {"count": 9, "total_s": 9.0}}}
    info = {"config": cell.config, "traffic": cell.traffic,
            "window": {"context_tokens": 3200000},
            "peak": {"hbm_bytes_per_s": 819e9},
            "lib": {"trace_reduce": tr, "family": family.adapter,
                    "bytes_model": family.bytes_model}}
    stats = {"batch": {"steps": 100, "size_sum": 6300},
             "kv": {"positions_held": 100 * 180000,
                    "positions_whole": 100 * 300000},
             "moe": {"step_expert_rows": 100 * 200, "step_assignments": 252000,
                     "page_expert_rows": 10 * 110, "page_count": 10}}

    def read(name, stats=stats, trace=trace, info=info):
        return harness.load_plugin("layer_metrics", name).read(
            stats, {}, trace, info)

    return read, family.bytes_model, cell.config, info


def test_the_three_readers_on_a_synthetic_trace(reading):
    read, bm, conf, _ = reading
    assert read("swa.step_roofline_share") == pytest.approx(
        100 * bm.step_bytes_counted(conf, 180000, 200, 63, 16) / 819e9 / 0.02)
    assert read("swa.page_roofline_share") == pytest.approx(
        100 * bm.page_bytes_counted(conf, 0, 110, 16) / 819e9 / 0.01)
    assert read("kv.held_share") == pytest.approx(60.0)
    # the family through the accepted readers, as the other families
    assert read("step.device_ms") == pytest.approx(20.0)
    assert 0 < read("step.roofline_share") < read("swa.step_roofline_share")
    assert read("swa.step_roofline_share") < 100


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_that_finds_nothing_returns_nothing_and_does_not_raise(
        reading, harness, name):
    read, _, _, info = reading
    # a program without the counters (the parent), an empty window
    assert read(name, stats={"batch": {"steps": 100, "size_sum": 6300}}) is None
    idle = {"batch": {"steps": 0, "size_sum": 0},
            "kv": {"positions_held": 0, "positions_whole": 0},
            "moe": {"step_expert_rows": 0, "step_assignments": 0,
                    "page_expert_rows": 0, "page_count": 0}}
    assert read(name, stats=idle) is None
    traced = name.startswith("swa.")
    assert (read(name, trace=None) is None) == traced
    assert (read(name, trace={"programs": {}}) is None) == traced
    # another family's adapter and bytes model: nothing of this one to read
    other_cell = harness.load_cell("ling-3.0-flash-vl-ep4-d7.state-decode")
    other = harness.load_family(other_cell.config)
    theirs = dict(info, config=other_cell.config)
    theirs["lib"] = dict(info["lib"], family=other.adapter,
                         bytes_model=other.bytes_model)
    if traced:
        assert read(name, info=theirs) is None


# -- a whole tiny cell ------------------------------------------------------------------


def test_a_tiny_cell_of_the_family_runs_whole_and_is_correct(tiny_copy):
    """All of ``run_cell`` but its look for a chip, on the committed
    adapter, reference, bytes model and warmer."""
    import jax

    h = load(str(tiny_copy / "benchmark/harness.py"), "bench_harness_swa_copy")
    cell = h.load_cell("tiny-swa.tiny-mixed")
    family = h.load_family(cell.config)
    for mod, rel in ((family.adapter, "families/swa_gqa_moe.py"),
                     (family.reference, "references/swa_gqa_moe.py"),
                     (family.bytes_model, "bytes_models/swa_gqa_moe.py")):
        assert mod.__file__ == str(tiny_copy / "benchmark" / rel)
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        line = h.run_cell("tiny-swa.tiny-mixed", seed=2**31 + 36,
                          seconds=2.0, trace=False,
                          t_start=time.perf_counter(), platform="cpu")
    finally:
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


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_one_precision_lower_is_not_correct(seed):
    control = load(os.path.join(BENCH, "control.py"), "bench_control_swa")
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


# -- the mix ------------------------------------------------------------------------------


def test_the_mix_is_the_issues_sizes_and_what_it_warms_covers_them():
    sys.path.insert(0, os.path.join(BENCH, "generators"))
    try:
        import lognormal_turns
    finally:
        sys.path.pop(0)
    spec = traffic_file()
    sizes = lognormal_turns.pool(spec["params"])
    assert sorted(p for p, _ in sizes) == [
        79, 137, 186, 236, 287, 342, 404, 473, 554, 649, 766, 914, 1113,
        1406, 1913, 3298]
    assert sum(p for p, _ in sizes) == 12757
    assert sum(n for _, n in sizes) == 2296
    assert sum(p > 512 for p, _ in sizes) == 8      # past the window
    eng, warm = spec["engine"], spec["warm"]
    P = eng["page_tokens"]
    assert all(p % P for p, _ in sizes)
    assert (eng["max_active"], eng["max_batch"], eng["prefix_cache"]) == (
        64, 64, False)
    assert spec["params"]["clients"] == 64 and warm["requests"] == 64
    assert spec["expect"] == {"window_promotes_max": 0}
    seeds = {json.load(open(os.path.join(BENCH, "traffic", f)))["params"][
        "shape_seed"] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if f != "mixed-lengths.json"}
    assert spec["params"]["shape_seed"] not in seeds
    # every context the page program can be handed is warmed, and a fused
    # bucket names, a kind, table pages and pool rows that are warmed too
    assert warm["warmer"] == "paged_swa_moe"
    assert warm["prefill_context_pages"] > max(p for p, _ in sizes) // P
    longest = max(-(-(p + n) // P) for p, n in sizes)
    assert warm["fused_buckets"]
    for b, mp_f, n_f, mp_w, n_w in warm["fused_buckets"]:
        assert (b, mp_w) == (64, 32) and mp_f in (128, 256)
        assert n_f in warm["pool_rows"][0] and n_w in warm["pool_rows"][1]
    assert max(b[1] for b in warm["fused_buckets"]) >= longest
    # HOT holds every live page with the drop (both kinds counted), which
    # is fewer than it would be without: the census's two peaks are stated
    peak, undropped = eng["live_pages_peak"], eng[
        "live_pages_peak_without_the_drop"]
    assert peak < undropped and peak < eng["hot_pages"] <= 1.5 * peak
    assert 64 * 32 < peak
