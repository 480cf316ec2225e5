"""The ``swa_gqa_softmax_moe`` family and the cell of PR 42 through the
benchmark (CPU, tiny size): the entries found by NAME, the configuration
held to the catalog's numbers, the adapter's held keys, the bytes model
against the program's own parameter list, the three readers on a synthetic
trace, a whole tiny cell through ``run_cell`` with the COMMITTED adapter,
reference, bytes model and warmer, the control one precision lower, and the
mix's sizes. A CPU run proves counts and control flow, never a time or a
rate."""

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

CONFIG = "mellum2-12b-a2.5b-d8"
CELL = f"{CONFIG}.file-context"
NEW_METRICS = ("family.step_roofline_share", "family.page_roofline_share",
               "moe.page_touched_share")
CATALOG = os.path.join("/opt", "skills", "guides", "model-configs",
                       "architectures.jsonl")
# Every key of the catalog entry's config (model-configs guide,
# architectures.jsonl, Mellum2-12B-A2.5B-Instruct) but the one of the cut;
# the lists are held whole below.
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


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
    with open(os.path.join(BENCH, "traffic", "file-context.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    """A tiny configuration file of the family, shaped as Mellum2's is
    published (one head count, no router width, no gate, shared-expert or
    scaling key, rope groups without a partial factor): one period W W W F,
    a window of 20 positions (2.5 pages of 8), 16 experts of which 4 a
    token, all held."""
    from oncilla_tpu.models import SwaMoeConfig

    d = SwaMoeConfig.tiny_softmax(sliding_window=20).to_published()
    for key in ("num_attention_heads_per_layer", "router_experts",
                "first_expert", "shared_expert_intermediate_size",
                "moe_routed_scaling_factor", "gating", "scoring_func"):
        del d[key]
    for group in d["rope_parameters"].values():
        del group["partial_rotary_factor"]
    d.update({
        "name": "tiny-mellum", "source": "tests",
        "family": "swa_gqa_softmax_moe", "num_attention_heads": 8,
        "norm_topk_prob": True, "reduced": [], "assumed": {},
        "guarantees": {"cold_replicas": 2},
        "tolerance": {"max_abs_dlogit": 1e-3, "argmax_share": 1.0,
                      "why": "float32 on the CPU: the paged path and the "
                             "plain forward differ by summation order alone"},
        "tolerance_served": {"max_logit_gap": 1e-3, "why": "as tolerance"},
    })
    return d


TINY_TRAFFIC = {
    "generator": "lognormal_turns",
    "why": "4 callers, everything HOT, every prompt past the tiny window",
    "who": "tests",
    "params": {"clients": 4, "arrivals": {"kind": "closed"},
               "shared_prefix_tokens": 0,
               "prompt": {"median": 40, "sigma": 0.3, "min": 25, "max": 70},
               "new_tokens": {"median": 9, "sigma": 0.4, "min": 5, "max": 14},
               "avoid_multiple_of": 8, "pool": 12, "shape_seed": 2},
    "engine": {"page_tokens": 8, "max_active": 4, "max_batch": 4,
               "prefix_cache": False, "prefetch_workers": 2, "hot_pages": 96,
               "warm_pages": 2, "cold_pages": 64, "cold_daemons": 3},
    "warm": {"warmer": "paged_swa_moe", "prefill_context_pages": 9,
             "fused_buckets": [[4, 16, 32, 4, 16], [4, 16, 64, 4, 16]],
             "pool_rows": [[32, 64], [16]],
             "ramp": [[1, 1], [2, 1]], "requests": 12},
    "expect": {"window_promotes_max": 0},
}


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_mellum")


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration of the committed
    family and a tiny mix added; the family's files are the committed ones."""
    tmp = tmp_path_factory.mktemp("bench_mellum")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (tmp / "benchmark/configs/tiny-mellum.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "benchmark/traffic/tiny-files.json").write_text(
        json.dumps(TINY_TRAFFIC))
    b = bench_json()
    b["configs"].append({"name": "tiny-mellum", "source": "tests",
                         "reduced": [], "why": "tests",
                         "file": "benchmark/configs/tiny-mellum.json"})
    b["workloads"].append({"name": "tiny-mellum.tiny-files",
                           "config": "tiny-mellum", "traffic": "tiny-files",
                           "chips": 1, "why": "tests"})
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-mellum.tiny-files")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


# -- the entries, by name ---------------------------------------------------------


def test_the_configuration_and_the_cell_are_entries_found_by_name(harness):
    b = bench_json()
    conf = by_name(b["configs"], CONFIG)
    assert conf["reduced"] == ["num_hidden_layers"] == config_file()["reduced"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["source"] == config_file()["source"] and len(conf["why"]) <= 200
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "file-context", 1)
    assert len(cell["why"]) <= 200
    # the new entries follow the accepted ones: nothing that was there moved
    assert [c["name"] for c in b["configs"]].index(CONFIG) == 6
    assert [w["name"] for w in b["workloads"]].index(CELL) == 7
    names = [m["name"] for m in b["per_layer"]]
    at = names.index("tiers.walks_per_page_placed") + 1
    assert names[at:at + 3] == list(NEW_METRICS)
    assert b["run_seconds"] == 45
    # the configuration resolves to the program's config through the adapter
    loaded = harness.load_cell(CELL)
    family = harness.load_family(loaded.config)
    assert family.name == "swa_gqa_softmax_moe"
    cfg = family.adapter.program_config(loaded.config)
    assert (cfg.gating, cfg.scoring_func) == ("none", "softmax")
    assert {m["name"] for m in loaded.per_layer} >= set(NEW_METRICS)


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


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_as_the_catalog_has_it(key):
    assert config_file()[key] == PUBLISHED[key]


def test_the_file_holds_every_number_of_the_catalog_entry():
    """Where the catalog is at hand: every top-level number and every group
    of its config, under the same key, but the one key of the cut."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not installed here")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Mellum2-12B-A2.5B-Instruct"' in line)
    file = config_file()
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
        else:
            assert file[key] == value, key


def test_the_groups_and_the_lists_are_copied_whole():
    file = config_file()
    assert file["rope_parameters"] == ROPE
    assert file["layer_types"] == PERIOD * 7
    assert file["mlp_layer_types"] == ["sparse"] * 28
    assert file["torch_dtype"] == "bfloat16"


def test_the_cut_is_depth_alone_and_keeps_the_guides_floors():
    file = config_file()
    assert file["published"] == {"num_hidden_layers": 28}
    assert file["num_hidden_layers"] == 8
    kept = file["layer_types"][:8]
    assert kept == PERIOD * 2          # two whole periods, at their index
    for key in ("assumed", "deployment", "guarantees", "reduced_why"):
        assert file[key]
    for key in ("qk_norm", "window", "mtp", "routing", "rotary", "cache",
                "weights", "store_dtype"):
        assert file["assumed"][key]
    assert "PROVISIONAL" not in json.dumps(file)
    for tol in ("tolerance", "tolerance_served"):
        assert len(file[tol]["why"]) > 200


HELD = [("gating", "per-head"), ("scoring_func", "sigmoid"),
        ("shared_expert_intermediate_size", 896), ("n_shared_experts", 1),
        ("first_k_dense_replace", 1), ("mlp_only_layers", [0]),
        ("moe_routed_scaling_factor", 2.5), ("norm_topk_prob", False),
        ("attention_bias", True), ("tie_word_embeddings", True),
        ("hidden_act", "gelu")]


@pytest.mark.parametrize("key,value", HELD)
def test_the_adapter_raises_on_what_the_program_does_not_compute(
        harness, key, value):
    family = harness.load_family(config_file())
    family.adapter.program_config(config_file())
    with pytest.raises(ValueError, match=key):
        family.adapter.program_config({**config_file(), key: value})


def test_the_adapter_raises_on_a_dense_layer_or_another_rotary(harness):
    family = harness.load_family(config_file())
    file = config_file()
    kinds = list(file["mlp_layer_types"])
    kinds[20] = "dense"     # a layer that is not kept: no matter
    family.adapter.program_config({**file, "mlp_layer_types": kinds})
    kinds[3] = "dense"
    with pytest.raises(ValueError, match="mlp_layer_types"):
        family.adapter.program_config({**file, "mlp_layer_types": kinds})
    for kind, other in (("full_attention", "default"),
                        ("sliding_attention", "yarn")):
        rope = json.loads(json.dumps(file["rope_parameters"]))
        rope[kind]["rope_type"] = other
        with pytest.raises(ValueError, match="rope_type"):
            family.adapter.program_config({**file, "rope_parameters": rope})


def test_the_adapter_reads_what_the_published_config_leaves_out(harness):
    """Every field of the program's config: a Laguna default left in would
    compute a gate, a shared expert, half a head rotated or a scaling."""
    family = harness.load_family(config_file())
    cfg = family.adapter.program_config(config_file())
    assert cfg.num_attention_heads_per_layer == (32,) * 8
    assert cfg.layer_types == tuple(PERIOD * 2)
    assert cfg.mlp_layer_types == ("sparse",) * 8
    assert cfg.experts_held == (0, 64) and cfg.n_routed_experts == 64
    assert cfg.first_k_dense_replace == 0 and cfg.n_expert_layers == 8
    assert cfg.shared_expert_intermediate_size == 0
    assert cfg.moe_routed_scaling_factor == 1.0
    assert (cfg.full_partial_rotary_factor,
            cfg.window_partial_rotary_factor) == (1.0, 1.0)
    assert (cfg.full_rope_theta, cfg.window_rope_theta) == (500000, 500000)
    assert (cfg.full_factor, cfg.full_original_max_position_embeddings,
            cfg.full_beta_fast, cfg.full_beta_slow) == (16, 8192, 32, 1)
    assert abs(cfg.full_attention_factor - 1.2772588722239782) < 1e-12
    assert (cfg.sliding_window, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.vocab, cfg.hidden_size) == (
        1024, 8, 896, 98304, 2304)
    assert (cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps,
            cfg.dtype) == (4, 128, 1e-6, "bfloat16")


# -- the bytes model ----------------------------------------------------------------


def test_bytes_model_counts_the_programs_own_parameters(harness):
    """The bytes model is shapes alone and imports nothing of the program;
    here it is held to the program's parameter list, leaf by leaf."""
    from oncilla_tpu.models import SwaMoeConfig
    from oncilla_tpu.serving.engine import ServingEngine

    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    bm = family.bytes_model
    conf = cell.config
    cfg = family.adapter.program_config(conf)
    module = sys.modules[SwaMoeConfig.__module__]
    spec = module.param_spec(cfg)
    assert not {"f_wg", "w_wg", "ws_gate", "w_gate", "e_bias"} & set(spec)
    size = {k: math.prod(shape) * (4 if dt == "float32" else 2)
            for k, (shape, _, dt) in spec.items()}
    routed = sum(size[k] for k in ("w_gate_e", "w_up_e", "w_down_e"))
    assert bm.weight_bytes(conf) == sum(size.values())
    assert bm.fixed_weight_bytes(conf) == (
        sum(size.values()) - routed - size["embed"])
    assert bm.expert_bytes(conf) * 64 * 8 == routed
    # the issue's count: 3.795 B parameters, 7.59 GB, 47 % of the chip
    params = sum(math.prod(shape) for shape, _, _ in spec.values())
    assert 3.7945e9 < params < 3.7955e9
    assert 7.59e9 < bm.weight_bytes(conf) < 7.60e9
    assert bm.attention_bytes(conf) // 2 == 21233664        # 21.23 M
    assert bm.expert_bytes(conf) // 2 == 6193152            # 6.19 M
    # a 16-token page of each kind; the store is built for the larger
    pages = bm.page_bytes(conf, 16)
    assert pages == {"full": 128 << 10, "window": 384 << 10}
    assert ServingEngine.page_nbytes(cfg, 16) == max(pages.values())
    assert bm.layer_position_bytes(conf) == 2 * 4 * 128 * 2
    # the least a step moves: one seat, one token's experts, one window
    least = bm.decode_step_bytes(conf, 2000)
    assert least == bm.step_bytes_counted(conf, 2 * 2000 + 6 * 1024, 64, 1)
    assert bm.decode_step_bytes(conf, 100) == bm.step_bytes_counted(
        conf, 8 * 100, 64, 1)
    assert (bm.step_bytes_counted(conf, 0, 1, 1)
            - bm.step_bytes_counted(conf, 0, 0, 1) == bm.expert_bytes(conf))
    assert (bm.step_bytes_counted(conf, 10, 0, 1)
            - bm.step_bytes_counted(conf, 0, 0, 1) == 10 * 2048)
    assert (bm.step_bytes_counted(conf, 0, 0, 16)
            - bm.step_bytes_counted(conf, 0, 0, 1)
            == 15 * 2 * 8 * 16 * 2048)
    assert bm.page_bytes_counted(conf, 7, 9) == bm.step_bytes_counted(
        conf, 7, 9, 1)


# -- the readers ----------------------------------------------------------------------


@pytest.fixture()
def reading(harness):
    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    tr = harness.load_plugin("", "trace_reduce")
    trace = {"programs": {
        "jit_swa_decode_batch_step_jit": {"count": 50, "total_s": 0.5},
        "jit_swa_decode_page_jit": {"count": 40, "total_s": 0.4},
        "jit_kda_decode_batch_step_jit": {"count": 9, "total_s": 9.0}}}
    info = {"config": cell.config, "traffic": cell.traffic,
            "window": {"context_tokens": 100 * 16 * 1700},
            "peak": {"hbm_bytes_per_s": 819e9},
            "lib": {"trace_reduce": tr, "family": family.adapter,
                    "bytes_model": family.bytes_model}}
    stats = {"batch": {"steps": 100, "size_sum": 1600},
             "kv": {"positions_held": 100 * 16 * (2 * 1700 + 6 * 1024),
                    "positions_whole": 100 * 16 * 8 * 1700,
                    "page_positions_read": 40 * (2 * 700 + 6 * 1024)},
             "moe": {"step_expert_rows": 100 * 450, "step_assignments": 102400,
                     "page_expert_rows": 40 * 451, "page_count": 40}}

    def read(name, stats=stats, trace=trace, info=info):
        return harness.load_plugin("layer_metrics", name).read(
            stats, {}, trace, info)

    return read, family.bytes_model, cell.config, info


def test_the_three_readers_on_a_synthetic_trace(reading):
    read, bm, conf, _ = reading
    assert read("family.step_roofline_share") == pytest.approx(
        100 * bm.step_bytes_counted(conf, 16 * (2 * 1700 + 6 * 1024), 450,
                                    16, 16) / 819e9 / 0.01)
    assert read("family.page_roofline_share") == pytest.approx(
        100 * bm.page_bytes_counted(conf, 2 * 700 + 6 * 1024, 451, 16)
        / 819e9 / 0.01)
    assert read("moe.page_touched_share") == pytest.approx(
        100 * 451 / (64 * 8))
    # the family through the accepted readers, as the other families
    assert read("step.device_ms") == pytest.approx(10.0)
    assert 0 < read("step.roofline_share") < read(
        "family.step_roofline_share") < 100
    assert read("family.step_roofline_share") == pytest.approx(
        read("swa.step_roofline_share"))
    # with the context counted the page share reads higher than swa's
    assert read("family.page_roofline_share") > read(
        "swa.page_roofline_share")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_that_finds_nothing_returns_nothing_and_does_not_raise(
        reading, harness, name):
    read, _, _, info = reading
    # a program without the counters (the parent), an empty window
    assert read(name, stats={"batch": {"steps": 100, "size_sum": 1600}}) is None
    idle = {"batch": {"steps": 0, "size_sum": 0},
            "kv": {"positions_held": 0, "positions_whole": 0,
                   "page_positions_read": 0},
            "moe": {"step_expert_rows": 0, "step_assignments": 0,
                    "page_expert_rows": 0, "page_count": 0}}
    assert read(name, stats=idle) is None
    traced = name.startswith("family.")
    assert (read(name, trace=None) is None) == traced
    assert (read(name, trace={"programs": {}}) is None) == traced
    if name == "family.page_roofline_share":
        # a parent's kv block, without the page counter
        parent = {"batch": {"steps": 100, "size_sum": 1600},
                  "kv": {"positions_held": 5, "positions_whole": 9},
                  "moe": {"step_expert_rows": 1, "step_assignments": 1,
                          "page_expert_rows": 9, "page_count": 2}}
        assert read(name, stats=parent) is None
    # another family's bytes model without the functions: nothing to read
    other_cell = harness.load_cell("xing4.0-29b-a4b-d6.decode-heavy")
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

    h = load(str(tiny_copy / "benchmark/harness.py"),
             "bench_harness_mellum_copy")
    cell = h.load_cell("tiny-mellum.tiny-files")
    family = h.load_family(cell.config)
    for mod, rel in ((family.adapter, "families/swa_gqa_softmax_moe.py"),
                     (family.reference, "references/swa_gqa_softmax_moe.py"),
                     (family.bytes_model,
                      "bytes_models/swa_gqa_softmax_moe.py")):
        assert mod.__file__ == str(tiny_copy / "benchmark" / rel)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        line = h.run_cell("tiny-mellum.tiny-files", seed=2**31 + 42,
                          seconds=3.0, trace=False,
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


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_the_control_one_precision_lower_is_not_correct(seed):
    control = load(os.path.join(BENCH, "control.py"), "bench_control_mellum")
    out = control.control(tiny_config(), seed, tokens=40)
    assert out["lower"] == "bfloat16" and out["correct"] is False
    assert out["max_abs_dlogit"]["value"] > 3 * out["max_abs_dlogit"]["limit"]


def test_a_program_without_the_switches_is_refused_at_once(tmp_path):
    """The parent's window family has no ``gating`` / ``scoring_func``: the
    adapter refuses it when it is loaded, before any device is touched,
    where it would otherwise serve Laguna's gate and router under this
    name."""
    import dataclasses

    from oncilla_tpu import models

    fields = {f.name: (f.type, f.default)
              for f in dataclasses.fields(models.SwaMoeConfig)
              if f.name not in ("gating", "scoring_func")}
    old = dataclasses.make_dataclass(
        "SwaMoeConfig", [(n, t, dataclasses.field(default=d))
                         for n, (t, d) in fields.items()], frozen=True)
    path = os.path.join(BENCH, "families", "swa_gqa_softmax_moe.py")
    saved = models.SwaMoeConfig
    models.SwaMoeConfig = old
    try:
        with pytest.raises(ImportError, match="scoring_func"):
            load(path, "adapter_on_a_parent")
    finally:
        models.SwaMoeConfig = saved


# -- the mix ------------------------------------------------------------------------------


def test_the_mix_is_the_issues_sizes_and_what_it_warms_covers_them():
    sys.path.insert(0, os.path.join(BENCH, "generators"))
    try:
        import lognormal_turns
    finally:
        sys.path.pop(0)
    spec = traffic_file()
    sizes = lognormal_turns.pool(spec["params"])
    assert len(sizes) == 16
    prompts = sorted(p for p, _ in sizes)
    assert prompts[0] == 1041 and prompts[-1] == 2044
    assert all(p > 1024 for p in prompts)      # every one past the window
    assert sum(p for p, _ in sizes) == 23018
    assert sum(n for _, n in sizes) == 4590
    assert sorted(n for _, n in sizes)[::15] == [101, 650]
    assert max(p + n for p, n in sizes) == 2694
    eng, warm = spec["engine"], spec["warm"]
    P = eng["page_tokens"]
    assert P == 16 and all(p % P for p, _ in sizes)
    assert (eng["max_active"], eng["max_batch"], eng["prefix_cache"]) == (
        16, 16, False)
    assert spec["params"]["clients"] == 16 and warm["requests"] == 16
    assert eng["warm_pages"] == 64
    assert spec["expect"] == {"window_promotes_max": 0}
    seeds = {json.load(open(os.path.join(BENCH, "traffic", f)))["params"][
        "shape_seed"] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if f != "file-context.json"}
    assert spec["params"]["shape_seed"] not in seeds
    # every context the page program can be handed is warmed, and a fused
    # bucket names, a kind, table pages and pool rows that are warmed too
    assert warm["warmer"] == "paged_swa_moe"
    assert warm["prefill_context_pages"] > max(prompts) // P
    longest = max(-(-(p + n) // P) for p, n in sizes)
    for b, mp_f, n_f, mp_w, n_w in warm["fused_buckets"]:
        assert b in (8, 16) and mp_w == 64 and mp_f == 256
        assert n_f in warm["pool_rows"][0] and n_w in warm["pool_rows"][1]
    assert max(b[1] for b in warm["fused_buckets"]) >= longest
    # HOT holds every live page with the drop, 1.25 x the census's peak
    peak, undropped = eng["live_pages_peak"], eng[
        "live_pages_peak_without_the_drop"]
    assert peak < undropped and peak < eng["hot_pages"] <= 1.3 * peak
