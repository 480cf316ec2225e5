"""The ``kda_latent_moe`` family and the two cells of PR 33 through the
benchmark (CPU, tiny size): the entries found by NAME, the configuration
held to the catalog's widths, the bytes model against the program's own
parameter list, the four readers on a synthetic trace, a whole tiny cell
through ``run_cell`` with the COMMITTED adapter, reference, bytes model and
warmer, the control one precision lower, the family's census, and
``sessions-fit`` held to ``sessions-overcommit``. A CPU run proves counts
and control flow, never a time or a rate."""

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

CONFIG = "ling-3.0-flash-vl-ep4-d7"
CELL = f"{CONFIG}.state-decode"
FIT = "mistral-7b-v0.1-d16.sessions-fit"
NEW_METRICS = ("kda.step_roofline_share", "kda.page_roofline_share",
               "moe.held_touched_share", "carry.seats_kept_share")
# Every number of the catalog entry's config (model-configs guide,
# architectures.jsonl, Ling-3.0-flash-VL), but the four keys of the cut.
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "hidden_size": 2560, "intermediate_size": 6144,
    "max_position_embeddings": 131072, "moe_intermediate_size": 768,
    "num_experts_per_tok": 8, "num_attention_heads": 32, "q_lora_rank": None,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "partial_rotary_factor": 0.5,
    "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
    "n_group": 8, "topk_group": 4, "use_qk_norm": True,
    "score_function": "sigmoid", "moe_shared_expert_intermediate_size": 768,
    "layer_group_size": 6, "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1, "linear_silu": True, "rotary_dim": 64,
    "use_mla_nope": False, "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
}
CUT = {"num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
       "num_experts": (512, 128), "vocab_size": (157184, 39296)}


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


def tiny_config() -> dict:
    """A tiny configuration file of the family: every key the adapter holds
    at the value it holds it to, seven layers of period six, 16 experts in
    8 groups of which this chip holds the first 8."""
    from oncilla_tpu.models import KdaLatentConfig

    d = KdaLatentConfig.tiny(
        num_hidden_layers=7, layer_group_size=6, n_group=8, topk_group=4,
        num_experts=8, router_experts=16).to_published()
    d.update({
        "name": "tiny-kda", "source": "tests", "family": "kda_latent_moe",
        "score_function": "sigmoid", "norm_topk_prob": True,
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
    "warm": {"warmer": "paged_kda_latent", "prefill_context_pages": 4,
             "fused_buckets": [[4, 4, 16]], "pool_rows": [16, 32],
             "ramp": [[1, 1], [2, 1]], "requests": 6},
    "expect": {"window_promotes_max": 0},
}


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_kda")


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration of the committed
    family and a tiny mix added; the family's files are the committed ones."""
    tmp = tmp_path_factory.mktemp("bench_kda")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (tmp / "benchmark/configs/tiny-kda.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "benchmark/traffic/tiny-state.json").write_text(
        json.dumps(TINY_TRAFFIC))
    b = bench_json()
    b["configs"].append({"name": "tiny-kda", "source": "tests",
                         "reduced": [], "why": "tests",
                         "file": "benchmark/configs/tiny-kda.json"})
    b["workloads"].append({"name": "tiny-kda.tiny-state",
                           "config": "tiny-kda", "traffic": "tiny-state",
                           "chips": 1, "why": "tests"})
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-kda.tiny-state")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


# -- the entries, by name ---------------------------------------------------------


def test_the_configuration_and_both_cells_are_entries_found_by_name():
    b = bench_json()
    conf = by_name(b["configs"], CONFIG)
    assert conf["reduced"] == list(CUT) == config_file()["reduced"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["source"] == config_file()["source"] and len(conf["why"]) <= 200
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "state-decode", 1)
    fit = by_name(b["workloads"], FIT)
    assert (fit["config"], fit["traffic"], fit["chips"]) == (
        "mistral-7b-v0.1-d16", "sessions-fit", 1)
    assert len(cell["why"]) <= 200 and len(fit["why"]) <= 200
    # nothing that was there moved: the accepted entries lead their lists
    assert [c["name"] for c in b["configs"]][:3] == [
        "internlm2-1.8b", "mistral-7b-v0.1-d16", "xing4.0-29b-a4b-d6"]
    assert [w["name"] for w in b["workloads"]][:3] == [
        "internlm2-1.8b.agent-shared",
        "mistral-7b-v0.1-d16.sessions-overcommit",
        "xing4.0-29b-a4b-d6.decode-heavy"]
    assert b["run_seconds"] == 45 and len(b["workloads"]) == 5


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_lists_the_new_cell_alone(name):
    b = bench_json()
    m = by_name(b["per_layer"], name)
    assert m["workloads"] == [CELL] and m["unit"] == "%"
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    # no accepted metric's list of cells changed
    for old, cells in (("prefix.reused_share", 1), ("dma.roofline_share", 2),
                       ("moe.experts_touched_share", 1),
                       ("moe.step_roofline_share", 1),
                       ("prefill.page_roofline_share", 1)):
        assert len(by_name(b["per_layer"], old)["workloads"]) == cells


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_as_the_catalog_has_it(key):
    assert config_file()[key] == PUBLISHED[key]


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
    assert file["router_experts"] == 512 and file["first_expert"] == 0
    assert file["num_experts_per_tok"] == 8 and file["torch_dtype"] == "bfloat16"
    period = file["layer_group_size"]
    following = file["num_hidden_layers"] - file["first_k_dense_replace"]
    assert following >= max(period, 4) and file["num_experts"] >= 8
    assert 8 * file["vocab_size"] >= file["published"]["vocab_size"]
    assert file["vocab_size"] * 4 == file["published"]["vocab_size"]
    # held experts are whole groups of the router's eight
    assert file["num_experts"] % (file["router_experts"] // file["n_group"]) == 0
    for lst in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(file[lst]) == 42
        assert not any(file[lst][:file["num_hidden_layers"]])
    for key in ("assumed", "deployment", "guarantees", "reduced_why"):
        assert file[key]
    # ids of the tower lie outside the slice: never drawn, never served
    assert min(file[k] for k in PUBLISHED if k.endswith("_token")) >= file[
        "vocab_size"]


@pytest.mark.parametrize("key,value", [
    ("n_group", 4), ("topk_group", 2), ("layer_group_size", 4),
    ("score_function", "softmax"), ("q_lora_rank", 768),
    ("kda_safe_gate", False)])
def test_the_adapter_raises_on_what_the_program_does_not_compute(
        harness, key, value):
    family = harness.load_family(config_file())
    family.adapter.program_config(config_file())
    with pytest.raises(ValueError, match=key):
        family.adapter.program_config({**config_file(), key: value})


def test_the_adapter_raises_on_a_kept_layer_that_clamps(harness):
    family = harness.load_family(config_file())
    limits = [0] * 42
    limits[6] = 4
    with pytest.raises(ValueError, match="clamps"):
        family.adapter.program_config(
            {**config_file(), "expert_swiglu_limit_list": limits})


# -- the bytes model ----------------------------------------------------------------


def test_bytes_model_counts_the_programs_own_parameters(harness):
    """The bytes model is shapes alone and imports nothing of the program;
    here it is held to the program's parameter list, leaf by leaf."""
    from oncilla_tpu.models import KdaLatentConfig
    from oncilla_tpu.serving.engine import ServingEngine

    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    bm = family.bytes_model
    cfg = family.adapter.program_config(cell.config)
    assert isinstance(cfg, KdaLatentConfig)
    assert cfg.latent_layers == (5,) and len(cfg.kda_layers) == 6
    assert cfg.experts_held == (0, 128) and cfg.n_routed_experts == 512
    module = sys.modules[KdaLatentConfig.__module__]
    spec = module.param_spec(cfg)
    size = {k: math.prod(shape) * (4 if dt == "float32" else 2)
            for k, (shape, _, dt) in spec.items()}
    routed = sum(size[k] for k in ("w_gate_e", "w_up_e", "w_down_e"))
    assert bm.weight_bytes(cell.config) == sum(size.values())
    assert bm.fixed_weight_bytes(cell.config) == (
        sum(size.values()) - routed - size["embed"])
    assert bm.expert_bytes(cell.config) * 128 * 6 == routed
    assert 10.3e9 < bm.weight_bytes(cell.config) < 10.4e9
    params = sum(math.prod(shape) for shape, _, _ in spec.values())
    assert 5.16e9 < params < 5.18e9
    # a 16-token page: one latent leaf of one cached layer
    assert (bm.page_bytes(cell.config, 16) == 1 * 16 * 576 * 4
            == ServingEngine.page_nbytes(cfg, 16))
    # the carry: what the family's leaves hold, float32
    leaves = module.PAGED_FAMILY.carry_leaves(cfg, 1)
    assert bm.carry_bytes(cell.config) == sum(
        math.prod(shape) * 4 for shape, _ in leaves)
    assert 13.4e6 < bm.carry_bytes(cell.config) < 13.5e6
    # the least a step moves: no held expert, one seat
    least = bm.decode_step_bytes(cell.config, 4000)
    assert least == bm.step_bytes_counted(cell.config, 4000, 0, 1)
    assert (bm.step_bytes_counted(cell.config, 4000, 1, 1) - least
            == bm.expert_bytes(cell.config))
    assert (bm.step_bytes_counted(cell.config, 4000, 0, 64) - least
            == 2 * 63 * bm.carry_bytes(cell.config))
    assert bm.page_bytes_counted(cell.config, 0, 9) == bm.step_bytes_counted(
        cell.config, 0, 9, 1)
    # ISSUE 33's estimate of a full step: ~8.6 GB
    full = bm.step_bytes_counted(cell.config, 64 * 500, 81 * 6, 64)
    assert 8.3e9 < full < 8.9e9


# -- the readers ----------------------------------------------------------------------


@pytest.fixture()
def reading(harness):
    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    tr = harness.load_plugin("", "trace_reduce")
    trace = {"programs": {
        "jit_kda_decode_batch_step_jit": {"count": 50, "total_s": 1.0},
        "jit_kda_decode_page_jit": {"count": 4, "total_s": 0.04},
        "jit_latent_decode_batch_step_jit": {"count": 9, "total_s": 9.0}}}
    info = {"config": cell.config, "window": {"context_tokens": 3200000},
            "peak": {"hbm_bytes_per_s": 819e9},
            "lib": {"trace_reduce": tr, "family": family.adapter,
                    "bytes_model": family.bytes_model}}
    stats = {"batch": {"steps": 100, "size_sum": 6300},
             "carry": {"seats_kept": 6200, "seats_written": 100},
             "moe": {"step_expert_rows": 100 * 480, "step_assignments": 302400,
                     "page_expert_rows": 10 * 170, "page_count": 10}}

    def read(name, stats=stats, trace=trace, info=info):
        return harness.load_plugin("layer_metrics", name).read(
            stats, {}, trace, info)

    return read, family.bytes_model, cell.config, info


def test_the_four_readers_on_a_synthetic_trace(reading):
    read, bm, conf, _ = reading
    assert read("kda.step_roofline_share") == pytest.approx(
        100 * bm.step_bytes_counted(conf, 32000, 480, 63) / 819e9 / 0.02)
    assert read("kda.page_roofline_share") == pytest.approx(
        100 * bm.page_bytes_counted(conf, 0, 170) / 819e9 / 0.01)
    assert read("moe.held_touched_share") == pytest.approx(
        100 * 480 / (128 * 6))
    assert read("carry.seats_kept_share") == pytest.approx(100 * 6200 / 6300)
    # the family through the accepted readers, as the other families
    assert read("step.device_ms") == pytest.approx(20.0)
    assert 0 < read("step.roofline_share") < read("kda.step_roofline_share") < 100


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_that_finds_nothing_returns_nothing_and_does_not_raise(
        reading, harness, name):
    read, _, _, info = reading
    # a program without the counters (the parent), an empty window
    assert read(name, stats={"batch": {"steps": 100, "size_sum": 6300}}) is None
    idle = {"batch": {"steps": 0, "size_sum": 0},
            "carry": {"seats_kept": 0, "seats_written": 0},
            "moe": {"step_expert_rows": 0, "step_assignments": 0,
                    "page_expert_rows": 0, "page_count": 0}}
    assert read(name, stats=idle) is None
    traced = name.startswith("kda.")
    assert (read(name, trace=None) is None) == traced
    assert (read(name, trace={"programs": {}}) is None) == traced
    # another family's adapter and bytes model: nothing of this one to read
    other = harness.load_family(
        harness.load_cell("xing4.0-29b-a4b-d6.decode-heavy").config)
    theirs = dict(info, config=harness.load_cell(
        "xing4.0-29b-a4b-d6.decode-heavy").config)
    theirs["lib"] = dict(info["lib"], family=other.adapter,
                         bytes_model=other.bytes_model)
    if name != "carry.seats_kept_share":
        assert read(name, info=theirs) is None


# -- a whole tiny cell ------------------------------------------------------------------


def test_a_tiny_cell_of_the_family_runs_whole_and_is_correct(tiny_copy):
    """All of ``run_cell`` but its look for a chip, on the committed
    adapter, reference, bytes model and warmer."""
    import jax

    from oncilla_tpu.serving.metrics import ServingStats

    h = load(str(tiny_copy / "benchmark/harness.py"), "bench_harness_kda_copy")
    cell = h.load_cell("tiny-kda.tiny-state")
    family = h.load_family(cell.config)
    for mod, rel in ((family.adapter, "families/kda_latent_moe.py"),
                     (family.reference, "references/kda_latent_moe.py"),
                     (family.bytes_model, "bytes_models/kda_latent_moe.py")):
        assert mod.__file__ == str(tiny_copy / "benchmark" / rel)
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS)
    carried = []
    note = ServingStats.note_carry

    def spy(self, kept=0, written=0):
        carried.append((kept, written))
        return note(self, kept=kept, written=written)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    ServingStats.note_carry = spy
    try:
        line = h.run_cell("tiny-kda.tiny-state", seed=2**31 + 33,
                          seconds=2.0, trace=False,
                          t_start=time.perf_counter(), platform="cpu")
    finally:
        ServingStats.note_carry = note
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
    # every fused step seated its carries: most kept, some written
    assert carried and all(1 <= k + w <= 4 for k, w in carried)
    assert sum(k for k, _ in carried) > sum(w for _, w in carried) > 0


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_one_precision_lower_is_not_correct(seed):
    control = load(os.path.join(BENCH, "control.py"), "bench_control_kda")
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
    census = load(os.path.join(BENCH, "census_kda_latent.py"),
                  "bench_census_kda")
    out = census.census("state-decode", [3], requests=2)
    with open(os.path.join(BENCH, "traffic", "state-decode.json")) as f:
        spec = json.load(f)
    warm = spec["warm"]
    assert out["fused_buckets"] and out["by_seed"][3]["ticks"] > 0
    assert (max(c for c, _ in out["prefill_context_pages"])
            < warm["prefill_context_pages"])
    # the family pads a context to a power-of-two number of pages
    assert all(n & (n - 1) == 0 for n, _ in out["prefill_padded_pages"])
    warmed = {tuple(b) for b in warm["fused_buckets"]}
    # A full house: batch 64, once the pool holds what a round leaves live.
    full = {tuple(b) for b, _ in out["fused_buckets"]
            if b[0] == 64 and b[2] >= 1024}
    assert full <= warmed
    assert {b[2] for b in warmed} <= set(warm["pool_rows"])
    assert all(b[0] == 64 for b in warmed)
    carry = out["by_seed"][3]["carry"]
    assert carry["seats_kept"] > carry["seats_written"] > 0
    assert out["by_seed"][3]["moe"]["step_expert_rows"] > 0
    assert spec["engine"]["hot_pages"] >= 64 * -(-1444 // 16)


# -- sessions-fit -------------------------------------------------------------------------


def traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_sessions_fit_is_sessions_overcommit_admitted_to_its_seats():
    fit, over = traffic("sessions-fit"), traffic("sessions-overcommit")
    assert fit["params"]["clients"] == 8 and over["params"]["clients"] == 16
    assert {**fit["params"], "clients": 16} == over["params"]
    assert fit["engine"]["max_active"] == fit["engine"]["max_batch"] == 8
    assert fit["engine"]["hot_pages"] == 256
    assert ({**fit["engine"], "max_active": 16, "hot_pages": 128}
            == over["engine"])
    assert fit["expect"] == {"window_promotes_max": 0}
    for key in ("warmer", "prefill_context_pages", "fused_buckets", "ramp",
                "requests"):
        assert fit["warm"][key] == over["warm"][key]


@pytest.mark.parametrize("name", ["state-decode", "sessions-fit"])
def test_every_live_page_of_a_new_mix_fits_hot(name):
    """No promote in the window: HOT holds every seat's longest request."""
    sys.path.insert(0, os.path.join(BENCH, "generators"))
    try:
        import lognormal_turns
    finally:
        sys.path.pop(0)
    spec = traffic(name)
    sizes = lognormal_turns.pool(spec["params"])
    P = spec["engine"]["page_tokens"]
    longest = max(-(-(p + n) // P) for p, n in sizes)
    assert spec["engine"]["max_active"] * longest <= spec["engine"]["hot_pages"]
    assert spec["engine"]["prefix_cache"] is False
    assert all(p % P for p, _ in sizes)
    assert spec["params"]["clients"] == spec["engine"]["max_active"]
