"""The ``conv_gqa_moe`` family and the cell of PR 40 through the benchmark
(CPU, tiny size): the entries found by NAME (no count of entries, no last
position), the configuration held to the catalog's numbers, the reference's
imports, the four readers on a synthetic trace and over a tiny engine's own
window, a whole tiny cell with the prefix cache on through ``run_cell`` with
the COMMITTED adapter, reference, bytes model and warmer, the control one
precision lower, and the mix's sizes. A CPU run proves counts and control
flow, never a time or a rate."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "lfm2-24b-a2b-d10"
MIX = "agent-prefix"
CELL = f"{CONFIG}.{MIX}"
NEW_METRICS = {
    "conv.step_roofline_share": ("%", "out_tok_s", "device_trace"),
    "conv.page_roofline_share": ("%", "ttft_ms_p90", "device_trace"),
    "prefix.carry_reused_share": ("%", "ttft_ms_p90", "program_counter"),
    "prefix.snapshot_ms_per_page": ("ms", "itl_ms_p95", "program_span"),
}
# Every number of the catalog entry's config (model-configs guide,
# architectures.jsonl, LFM2-24B-A2B) but the one key of the cut; the nested
# group and the list are held whole below.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_key_value_heads": 8, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
ROPE = {"rope_theta": 1000000, "rope_type": "default"}
LAYER_TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"]
               * 9 + ["full_attention", "conv"])
# The accepted entries, in their places (a later PR appends behind them).
ACCEPTED_CONFIGS = ["internlm2-1.8b", "mistral-7b-v0.1-d16",
                    "xing4.0-29b-a4b-d6", "ling-3.0-flash-vl-ep4-d7",
                    "laguna-s-2.1-ep4-d5"]
ACCEPTED_CELLS = ["internlm2-1.8b.agent-shared",
                  "mistral-7b-v0.1-d16.sessions-overcommit",
                  "xing4.0-29b-a4b-d6.decode-heavy",
                  "ling-3.0-flash-vl-ep4-d7.state-decode",
                  "mistral-7b-v0.1-d16.sessions-fit",
                  "laguna-s-2.1-ep4-d5.mixed-lengths"]


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
    with open(os.path.join(BENCH, "traffic", f"{MIX}.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    """A tiny configuration file of the family: every key the adapter holds
    at the value it holds it to, six layers C C A C C A."""
    from oncilla_tpu.models import ConvMoeConfig

    d = ConvMoeConfig.tiny().to_published()
    d.update({
        "name": "tiny-conv", "source": "tests", "family": "conv_gqa_moe",
        "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
        "reduced": [], "assumed": {}, "guarantees": {"cold_replicas": 2},
        "tolerance": {"max_abs_dlogit": 1e-3, "argmax_share": 1.0,
                      "why": "float32 on the CPU: the paged path and the "
                             "plain forward differ by summation order alone"},
        "tolerance_served": {"max_logit_gap": 1e-3, "why": "as tolerance"},
    })
    return d


TINY_TRAFFIC = {
    "generator": "lognormal_turns",
    "why": "4 callers behind a shared prefix of three pages, everything HOT",
    "who": "tests",
    "params": {"clients": 4, "arrivals": {"kind": "closed"},
               "shared_prefix_tokens": 24,
               "prompt": {"median": 12, "sigma": 0.6, "min": 3, "max": 30},
               "new_tokens": {"median": 9, "sigma": 0.4, "min": 5, "max": 14},
               "avoid_multiple_of": 8, "pool": 8, "shape_seed": 2},
    "engine": {"page_tokens": 8, "max_active": 4, "max_batch": 4,
               "prefix_cache": True, "prefetch_workers": 2, "hot_pages": 1024,
               "warm_pages": 2, "cold_pages": 64, "cold_daemons": 3},
    "warm": {"warmer": "paged_conv_moe",
             "prefill_padded_pages": [0, 1, 2, 4, 8],
             "fused_buckets": [[4, 8, 16]],
             "ramp": [[1, 1], [2, 2]], "requests": 8},
    "expect": {"window_promotes_max": 0, "prefix_reused_share_min": 0.3},
}


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_conv")


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark with a tiny configuration of the committed
    family and a tiny mix added; the family's files are the committed ones."""
    tmp = tmp_path_factory.mktemp("bench_conv")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    (tmp / "benchmark/configs/tiny-conv.json").write_text(
        json.dumps(tiny_config()))
    (tmp / "benchmark/traffic/tiny-prefix.json").write_text(
        json.dumps(TINY_TRAFFIC))
    b = bench_json()
    b["configs"].append({"name": "tiny-conv", "source": "tests",
                         "reduced": [], "why": "tests",
                         "file": "benchmark/configs/tiny-conv.json"})
    b["workloads"].append({"name": "tiny-conv.tiny-prefix",
                           "config": "tiny-conv", "traffic": "tiny-prefix",
                           "chips": 1, "why": "tests"})
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-conv.tiny-prefix")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


# -- the entries, by name ---------------------------------------------------------


def test_the_configuration_and_the_cell_are_entries_found_by_name():
    b = bench_json()
    conf = by_name(b["configs"], CONFIG)
    assert conf["reduced"] == ["num_hidden_layers"] == config_file()["reduced"]
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["source"] == config_file()["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert len(conf["why"]) <= 200
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    # the new cell is the configuration's only one, and no other entry
    # names the configuration's file
    assert [w["name"] for w in b["workloads"] if w["config"] == CONFIG] == [
        CELL]
    assert [c["name"] for c in b["configs"] if c["file"] == conf["file"]] == [
        CONFIG]
    # nothing that was there moved: the accepted entries lead their lists,
    # whatever a later PR appends
    assert [c["name"] for c in b["configs"]][:5] == ACCEPTED_CONFIGS
    assert [w["name"] for w in b["workloads"]][:6] == ACCEPTED_CELLS
    assert b["run_seconds"] == 45
    assert [(e["name"], e["bound"]) for e in b["end_to_end"]] == [
        ("out_tok_s", 0.05), ("ttft_ms_p90", 0.1), ("itl_ms_p95", 0.08),
        ("setup_s", 0.1)]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_lists_the_new_cell_alone(name):
    b = bench_json()
    m = by_name(b["per_layer"], name)
    unit, moves, source = NEW_METRICS[name]
    assert m["workloads"] == [CELL]
    assert (m["unit"], m["moves"], m["source"]) == (unit, moves, source)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["better"] == ("lower" if name.endswith("ms_per_page")
                           else "higher")
    assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    # a layer BENCHMARK.json already names keeps its name, letter for letter
    if name.startswith("prefix."):
        assert m["layer"] == by_name(b["per_layer"],
                                     "prefix.reused_share")["layer"]
    else:
        assert m["layer"] == "model steps (models/conv_moe.py)"
    # no accepted metric's list of cells changed: none names the new cell
    for old in b["per_layer"]:
        if old["name"] not in NEW_METRICS:
            assert CELL not in old.get("workloads", []), old["name"]
    for old, cells in (("prefix.reused_share", 1), ("dma.roofline_share", 2),
                       ("moe.experts_touched_share", 1),
                       ("kda.step_roofline_share", 1),
                       ("carry.seats_kept_share", 1),
                       ("swa.step_roofline_share", 1), ("kv.held_share", 1),
                       ("itl.p95_ms", 6),
                       ("tiers.scrub_dispatches_per_page", 6)):
        assert len(by_name(b["per_layer"], old)["workloads"]) == cells


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_as_the_catalog_has_it(key):
    assert config_file()[key] == PUBLISHED[key]


def test_the_nested_group_the_list_and_the_cut():
    file = config_file()
    assert file["rope_parameters"] == ROPE
    assert file["layer_types"] == LAYER_TYPES and len(LAYER_TYPES) == 40
    assert file["torch_dtype"] == "bfloat16" and file["family"] == (
        "conv_gqa_moe")
    assert file["published"] == {"num_hidden_layers": 40}
    assert file["num_hidden_layers"] == 10
    assert file["reduced"] == ["num_hidden_layers"]
    kept = file["layer_types"][:10]
    # both dense layers, then two whole periods: eight layers after the
    # dense ones where the floor is four, every expert and the vocabulary
    assert kept == ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 2
    assert file["num_hidden_layers"] - file["num_dense_layers"] >= 4
    for key in ("assumed", "deployment", "guarantees", "reduced_why"):
        assert file[key]
    for key in ("head_dim", "tie_word_embeddings", "final_norm", "qk_norm",
                "rotary", "in_proj", "convolution", "router", "store",
                "draft_head"):
        assert file["assumed"][key], key
    assert file["head_dim"] == 64 == (file["hidden_size"]
                                      // file["num_attention_heads"])
    assert "10 536 365 056" in file["reduced_why"]
    assert "TO SET" not in json.dumps(file)
    for tol in ("tolerance", "tolerance_served"):
        assert len(file[tol]["why"]) > 200


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("norm_topk_prob", False),
    ("use_expert_bias", False), ("tie_word_embeddings", False)])
def test_the_adapter_raises_on_what_the_program_does_not_compute(
        harness, key, value):
    family = harness.load_family(config_file())
    cfg = family.adapter.program_config(config_file())
    assert cfg.attn_layers == (2, 6) and len(cfg.conv_layers) == 8
    assert cfg.head_dim == 64 and cfg.rope_theta == 1e6
    with pytest.raises(ValueError, match=key):
        family.adapter.program_config({**config_file(), key: value})
    with pytest.raises(ValueError, match="rope_type"):
        family.adapter.program_config(
            {**config_file(),
             "rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}})


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "references", "conv_gqa_moe.py")) as f:
        source = f.read()
    imports = [line.strip() for line in source.splitlines()
               if line.strip().startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "oncilla" in line]
    assert {line.split()[1].split(".")[0] for line in imports} <= {
        "__future__", "functools", "jax", "numpy"}
    assert "oncilla_tpu" not in source.split('"""', 2)[2]
    # a handful of programs whatever the lengths compared: sequences and
    # rows are padded to blocks, no function is jitted by length
    assert "SEQ_BLOCK" in source and "static_argnames=(\"S\"" not in source


# -- the readers ----------------------------------------------------------------------


@pytest.fixture()
def reading(harness):
    cell = harness.load_cell(CELL)
    family = harness.load_family(cell.config)
    tr = harness.load_plugin("", "trace_reduce")
    trace = {"programs": {
        "jit_conv_decode_batch_step_jit": {"count": 50, "total_s": 1.0},
        "jit_conv_decode_page_jit": {"count": 4, "total_s": 0.04},
        "jit_kda_decode_batch_step_jit": {"count": 9, "total_s": 9.0}}}
    info = {"config": cell.config, "traffic": cell.traffic,
            "window": {"context_tokens": 100 * 70000, "prompt_tokens": 50000,
                       "reused_tokens": 48000},
            "peak": {"hbm_bytes_per_s": 819e9},
            "lib": {"trace_reduce": tr, "family": family.adapter,
                    "bytes_model": family.bytes_model}}
    stats = {"batch": {"steps": 100, "size_sum": 3100},
             "prefix": {"adoptions": 30, "carry_restores": 30,
                        "carry_snapshots": 120, "carry_bytes": 120 << 17},
             "moe": {"step_expert_rows": 100 * 440, "step_assignments": 99200,
                     "page_expert_rows": 10 * 300, "page_count": 10}}
    spans = {"prefix.snapshot": {"count": 120, "total_s": 0.3},
             "prefix.restore": {"count": 30, "total_s": 0.03}}

    def read(name, stats=stats, trace=trace, info=info, spans=spans):
        return harness.load_plugin("layer_metrics", name).read(
            stats, spans, trace, info)

    return read, family.bytes_model, cell.config, info


def test_the_four_readers_on_a_synthetic_window(reading):
    read, bm, conf, _ = reading
    assert read("conv.step_roofline_share") == pytest.approx(
        100 * bm.step_bytes_counted(conf, 70000, 440, 31) / 819e9 / 0.02)
    assert read("conv.page_roofline_share") == pytest.approx(
        100 * bm.page_bytes_counted(conf, 0, 300) / 819e9 / 0.01)
    assert read("prefix.carry_reused_share") == pytest.approx(96.0)
    assert read("prefix.snapshot_ms_per_page") == pytest.approx(2.5)
    # the family through the accepted readers, as the other families
    assert read("step.device_ms") == pytest.approx(20.0)
    assert 0 < read("step.roofline_share") < read("conv.step_roofline_share")
    assert read("conv.step_roofline_share") < 100
    assert read("conv.page_roofline_share") < 100
    assert read("prefix.reused_share") == pytest.approx(96.0)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_that_finds_nothing_returns_nothing_and_does_not_raise(
        reading, harness, name):
    read, _, _, info = reading
    # a program without the counters and spans (the parent), an empty window
    bare = {"batch": {"steps": 100, "size_sum": 3100},
            "prefix": {"hits": 5, "shared_bytes": 0, "extents": 3, "cow": 0}}
    assert read(name, stats=bare, spans={}) is None
    idle = {"batch": {"steps": 0, "size_sum": 0},
            "prefix": {"adoptions": 0, "carry_restores": 0,
                       "carry_snapshots": 0, "carry_bytes": 0},
            "moe": {"step_expert_rows": 0, "step_assignments": 0,
                    "page_expert_rows": 0, "page_count": 0}}
    assert read(name, stats=idle, spans={}) is None
    traced = name.startswith("conv.")
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


def test_restores_that_disagree_with_adoptions_read_nothing(reading):
    read, _, _, _ = reading
    off = {"prefix": {"adoptions": 30, "carry_restores": 29}}
    assert read("prefix.carry_reused_share", stats=off) is None
    # a family without a carry adopts and restores nothing
    dense = {"prefix": {"adoptions": 30, "carry_restores": 0}}
    assert read("prefix.carry_reused_share", stats=dense) is None


# -- a whole tiny cell ------------------------------------------------------------------


def test_a_tiny_cell_runs_whole_and_the_readers_read_its_own_window(tiny_copy):
    """All of ``run_cell`` but its look for a chip, on the committed
    adapter, reference, bytes model and warmer, the prefix cache on; then
    the four readers over the counters and spans of that window."""
    import jax

    h = load(str(tiny_copy / "benchmark/harness.py"), "bench_harness_conv_copy")
    cell = h.load_cell("tiny-conv.tiny-prefix")
    family = h.load_family(cell.config)
    for mod, rel in ((family.adapter, "families/conv_gqa_moe.py"),
                     (family.reference, "references/conv_gqa_moe.py"),
                     (family.bytes_model, "bytes_models/conv_gqa_moe.py")):
        assert mod.__file__ == str(tiny_copy / "benchmark" / rel)
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS)
    seen = {"delta": [], "spans": []}
    delta, span_totals = h.delta, h.span_totals

    def keep_delta(after, before):
        out = delta(after, before)      # recursive, through this wrapper
        if isinstance(after, dict) and "prefix" in after and "batch" in after:
            seen["delta"].append(out)
        return out

    def keep_spans():
        out = span_totals()
        seen["spans"].append(out)
        return out

    h.delta, h.span_totals = keep_delta, keep_spans
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        line = h.run_cell("tiny-conv.tiny-prefix", seed=2**31 + 40,
                          seconds=3.0, trace=False,
                          t_start=time.perf_counter(), platform="cpu")
    finally:
        h.delta, h.span_totals = delta, span_totals
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
    assert c["prefix_reused_share"]["value"] >= 0.3
    # the window's own counters and spans through the four readers
    stats_win = seen["delta"][0]
    spans_win = delta(seen["spans"][1], seen["spans"][0])
    prefix = stats_win["prefix"]
    assert prefix["carry_snapshots"] > 0 and prefix["carry_bytes"] > 0
    assert prefix["adoptions"] == prefix["carry_restores"] > 0
    assert spans_win["prefix.snapshot"]["count"] == prefix["carry_snapshots"]
    assert spans_win["prefix.restore"]["count"] == prefix["carry_restores"]
    steps = stats_win["batch"]["steps"]
    pages = stats_win["moe"]["page_count"]
    trace = {"programs": {
        "jit_conv_decode_batch_step_jit": {"count": steps,
                                           "total_s": steps * 1.0},
        "jit_conv_decode_page_jit": {"count": pages, "total_s": pages * 1.0}}}
    info = {"config": cell.config, "traffic": cell.traffic,
            "window": {"context_tokens": 1000 * steps,
                       "prompt_tokens": 400, "reused_tokens": 240},
            "peak": {"hbm_bytes_per_s": 819e9},
            "lib": {"trace_reduce": h.load_plugin("", "trace_reduce"),
                    "family": family.adapter,
                    "bytes_model": family.bytes_model}}
    values = {name: h.load_plugin("layer_metrics", name).read(
        stats_win, spans_win, trace, info) for name in NEW_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["prefix.carry_reused_share"] == pytest.approx(60.0)
    bm = family.bytes_model
    assert values["conv.step_roofline_share"] == pytest.approx(
        100 * bm.step_bytes_counted(
            cell.config, 1000, stats_win["moe"]["step_expert_rows"] / steps,
            stats_win["batch"]["size_sum"] / steps) / 819e9)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_one_precision_lower_is_not_correct(seed):
    control = load(os.path.join(BENCH, "control.py"), "bench_control_conv")
    out = control.control(tiny_config(), seed, tokens=40)
    assert out["lower"] == "bfloat16" and out["correct"] is False
    d = out["max_abs_dlogit"]
    assert d["value"] > 3 * d["limit"]


# -- the mix ------------------------------------------------------------------------------


def test_the_mix_is_the_issues_sizes_and_what_it_warms_covers_them():
    sys.path.insert(0, os.path.join(BENCH, "generators"))
    try:
        import lognormal_turns
    finally:
        sys.path.pop(0)
    spec = traffic_file()
    params, eng, warm = spec["params"], spec["engine"], spec["warm"]
    assert spec["generator"] == "lognormal_turns"
    assert params["shared_prefix_tokens"] == 2048 and params["pool"] == 16
    assert params["prompt"] == {"median": 48, "sigma": 0.7, "min": 8,
                                "max": 192}
    assert params["new_tokens"] == {"median": 96, "sigma": 0.6, "min": 24,
                                    "max": 256}
    assert params["arrivals"] == {"kind": "closed"} and params["clients"] == 32
    sizes = lognormal_turns.pool(params)
    P = eng["page_tokens"]
    assert P == 16 and all((2048 + p) % P for p, _ in sizes)
    assert all(8 <= p <= 192 and 24 <= n <= 256 for p, n in sizes)
    assert (eng["max_active"], eng["max_batch"], eng["prefix_cache"]) == (
        32, 32, True)
    assert (eng["hot_pages"], eng["warm_pages"]) == (8192, 64)
    assert spec["expect"] == {"window_promotes_max": 0,
                              "prefix_reused_share_min": 0.9}
    # 2048 of every prompt's tokens are the shared ones
    prompt = sum(2048 + p for p, _ in sizes)
    assert 2048 * len(sizes) / prompt > 0.95
    others = {json.load(open(os.path.join(BENCH, "traffic", f)))["params"][
        "shape_seed"] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if f != f"{MIX}.json"}
    assert params["shape_seed"] not in others
    # the window opens after 64 completed requests, and the ramp begins with
    # one caller and one request: the system prompt is published once
    assert warm["requests"] == 64 and warm["ramp"][0] == [1, 1]
    assert warm["warmer"] == "paged_conv_moe"
    # every padded context a prompt of the mix can reach is warmed, and a
    # fused bucket's table holds the longest request
    longest = max(-(-(2048 + p + n) // P) for p, n in sizes)
    most = max((2048 + p) // P for p, _ in sizes)
    padded = warm["prefill_padded_pages"]
    assert padded == sorted(padded) and padded[0] == 0
    assert all(n & (n - 1) == 0 for n in padded[1:])
    assert max(padded) >= most and 128 in padded
    assert max(b[1] for b in warm["fused_buckets"]) >= longest
    assert {b[0] for b in warm["fused_buckets"]} >= {1, 2, 4, 8, 16, 32}
