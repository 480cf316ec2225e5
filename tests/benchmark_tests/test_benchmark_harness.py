"""CPU tests of the benchmark's harness (``benchmark/``): generators,
window arithmetic, that everything in ``BENCHMARK.json`` resolves by name,
the tick loop on the tiny model, and that nothing is measured off the chip.
A CPU run proves counts and control flow, never a time or a rate."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def harness():
    return load(os.path.join(BENCH, "harness.py"), "bench_harness_under_test")


@pytest.fixture(scope="module")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def traffic_files():
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


# -- generators ---------------------------------------------------------------


@pytest.mark.parametrize("traffic", traffic_files())
def test_generator_is_seeded_and_inside_its_clips(harness, traffic):
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        spec = json.load(f)
    gen = harness.load_plugin("generators", spec["generator"])
    p = spec["params"]
    big = 2**31 + 12345   # the driver's seeds pass 32 signed bits
    a = gen.schedule(big, p, 32000)
    b = gen.schedule(big, p, 32000)
    c = gen.schedule(big + 1, p, 32000)
    n = p["pool"]
    reqs_a = [a["nth"](i) for i in range(n)]
    assert reqs_a == [b["nth"](i) for i in range(n)]
    reqs_c = [c["nth"](i) for i in range(n)]
    assert reqs_a != reqs_c
    shared = p["shared_prefix_tokens"]
    for r in reqs_a:
        own = len(r["tokens"]) - shared
        assert p["prompt"]["min"] <= own <= p["prompt"]["max"]
        assert p["new_tokens"]["min"] <= r["max_new_tokens"] <= p["new_tokens"]["max"]
        assert all(1 <= t < 32000 for t in r["tokens"])
        assert len(r["tokens"]) % p["avoid_multiple_of"] != 0
        assert r["tokens"][:shared] == reqs_a[0]["tokens"][:shared]
    # Every seed offers the same sizes in the same order, other token ids.
    sizes = lambda reqs: [  # noqa: E731
        (len(r["tokens"]), r["max_new_tokens"]) for r in reqs]
    assert sizes(reqs_a) == sizes(reqs_c)
    assert len(set(sizes(reqs_a))) > n // 2
    assert all(x["tokens"] != y["tokens"] for x, y in zip(reqs_a, reqs_c))
    assert a["clients"] == p["clients"]
    # The lognormal's median survives the quantiles and the clips.
    med = np.median([len(r["tokens"]) - shared for r in reqs_a])
    assert abs(med - p["prompt"]["median"]) <= 0.1 * p["prompt"]["median"] + 1


def test_open_loop_arrivals_are_seeded_and_keep_their_rate(harness):
    gen = harness.load_plugin("generators", "lognormal_turns")
    for spec in ({"kind": "poisson", "rate_per_s": 4.0},
                 {"kind": "bursts", "rate_per_s": 4.0, "burst": 5}):
        due = gen.arrivals(7, spec, 2000)
        assert due == gen.arrivals(7, spec, 2000) != gen.arrivals(8, spec, 2000)
        assert all(b >= a for a, b in zip(due, due[1:]))
        assert 2000 / due[-1] == pytest.approx(4.0, rel=0.15)
    assert gen.arrivals(7, {"kind": "closed"}, 10) is None
    params = {"clients": 3, "pool": 8, "arrivals": {"kind": "poisson", "rate_per_s": 2.0},
              "prompt": {"median": 10, "sigma": 0.5, "min": 4, "max": 30},
              "new_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 9}}
    s = gen.schedule(1, params, 100)
    assert s["clients"] == 0 and s["nth"](3)["at_s"] > s["nth"](0)["at_s"] > 0


# -- window arithmetic -----------------------------------------------------------


def test_window_numbers_on_a_hand_made_stamp_list(harness):
    Rec = harness.Rec
    t0, t1 = 10.0, 20.0
    recs = [
        # straddles the start: its tokens and inner gaps count, it does not
        Rec(0, 8.0, 4, 30, stamps=[9.0, 10.5, 11.0, 12.0], out=[1, 2, 3, 4]),
        # wholly inside
        Rec(1, 11.0, 3, 20, stamps=[12.0, 12.5, 13.5], out=[5, 6, 7]),
        # in flight at the end: counts for TTFT and its gaps inside
        Rec(2, 18.0, 3, 10, stamps=[19.0, 19.5, 20.5], out=[1, 1, 1]),
        # submitted inside, came back short: failed
        Rec(3, 15.0, 5, 10, stamps=[16.0, 17.0], out=[1, 2]),
        # submitted inside, a token outside the vocabulary: failed
        Rec(4, 15.0, 2, 10, stamps=[16.0, 16.25], out=[1, 99999]),
        # after the window: nothing of it counts
        Rec(5, 21.0, 1, 10, stamps=[22.0], out=[1]),
    ]
    win = harness.window_numbers(recs, t0, t1, vocab=100)
    assert win["tokens"] == 3 + 3 + 2 + 2 + 2
    assert sorted(win["gaps"]) == sorted([0.5, 1.0, 0.5, 1.0, 0.5, 1.0, 0.25])
    assert sorted(win["ttfts"]) == [1.0, 1.0, 1.0, 1.0]
    assert (win["attempted"], win["failed"]) == (4, 2)
    assert win["prompt_tokens"] == 20 + 10 + 10 + 10
    # token k of a request read prompt + k positions
    assert win["context_tokens"] == (
        (31 + 32 + 33) + (20 + 21 + 22) + (10 + 11) + (10 + 11) + (10 + 11))
    vals = harness.end_to_end_values(win, setup_s=3.0)
    assert vals["out_tok_s"] == pytest.approx(12 / 10.0)
    assert vals["ttft_ms_p90"] == pytest.approx(1000.0)
    assert vals["itl_ms_p95"] == pytest.approx(
        1e3 * float(np.percentile([0.5, 1.0, 0.5, 1.0, 0.5, 1.0, 0.25], 95)))
    assert vals["setup_s"] == 3.0
    assert harness.percentile([1, 2, 3, 4], 50) == 2.5
    empty = harness.end_to_end_values(
        harness.window_numbers([], t0, t1, vocab=100), setup_s=1.0)
    assert "ttft_ms_p90" not in empty and empty["out_tok_s"] == 0


def test_delta_subtracts_nested_counters(harness):
    after = {"a": 5, "b": {"c": 2.5, "d": {"hbm>host": 3}}, "name": "x", "new": 1}
    before = {"a": 2, "b": {"c": 0.5, "d": {}}, "name": "x"}
    assert harness.delta(after, before) == {
        "a": 3, "b": {"c": 2.0, "d": {"hbm>host": 3}}, "name": "x", "new": 1}


# -- BENCHMARK.json resolves by name ------------------------------------------------


def test_every_entry_of_benchmark_json_resolves(harness, bench_json):
    b = bench_json
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert w["chips"] == 1 and len(w["why"]) <= 200 and "\n" not in w["why"]
        gen = harness.load_plugin("generators", cell.traffic["generator"])
        assert callable(gen.schedule)
        warmer = harness.load_plugin("warmers", cell.traffic["warm"]["warmer"])
        assert callable(warmer.warm)
        for key in ("page_tokens", "max_active", "max_batch", "prefix_cache",
                    "hot_pages", "warm_pages", "cold_pages", "cold_daemons"):
            assert key in cell.traffic["engine"]
        family = harness.load_family(cell.config)
        assert family.adapter.program_config(cell.config).head_dim == 128
        assert callable(family.reference.logits_at)
        assert callable(family.bytes_model.decode_step_bytes)
        assert {m["name"] for m in cell.end_to_end} == e2e
        assert cell.per_layer
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
        assert set(conf["tolerance"]) >= {"max_abs_dlogit", "argmax_share", "why"}
        assert set(conf["tolerance_served"]) >= {"max_logit_gap", "why"}
        assert conf["guarantees"]["cold_replicas"] == 2
    for m in b["per_layer"]:
        assert callable(harness.load_plugin("layer_metrics", m["name"]).read)
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    names = ([m["name"] for m in b["end_to_end"] + b["per_layer"]]
             + [w[k] for w in b["workloads"] for k in ("name", "config", "traffic")]
             + [c["name"] for c in b["configs"]]
             + [k for c in b["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in b["end_to_end"] + b["per_layer"])
    assert len({m["name"] for m in b["end_to_end"] + b["per_layer"]}) == len(
        b["end_to_end"] + b["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in b["end_to_end"])
    # one name, letter for letter, per layer
    layers = {}
    for m in b["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    for path in b["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (dirpath, f)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_an_unknown_name_is_refused_not_defaulted(harness):
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such-cell")
    with pytest.raises(harness.Refused):
        harness.load_plugin("layer_metrics", "no.such.metric")
    with pytest.raises(KeyError):
        harness.peak_of("TPU v9 imaginary")
    assert harness.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("family", [None, "", "no_such_family"])
def test_a_configuration_without_a_known_family_is_refused(harness, family):
    """No family is the default: a configuration that names none, or one
    with no adapter file, cannot run."""
    conf = dict(TINY_CONFIG)
    if family is None:
        del conf["family"]
    else:
        conf["family"] = family
    with pytest.raises(harness.Refused):
        harness.load_family(conf)


def config_files():
    return sorted(os.listdir(os.path.join(BENCH, "configs")))


@pytest.mark.parametrize("name", config_files())
def test_every_configuration_names_a_family_that_loads(harness, name):
    with open(os.path.join(BENCH, "configs", name)) as f:
        conf = json.load(f)
    family = harness.load_family(conf)
    assert family.name == conf["family"]
    assert callable(family.adapter.program_config)
    assert callable(family.adapter.init_params)
    assert family.adapter.DECODE_STEP_PROGRAM
    assert family.reference.__file__ == os.path.join(
        BENCH, "references", f"{family.adapter.REFERENCE}.py")
    assert family.bytes_model.__file__ == os.path.join(
        BENCH, "bytes_models", f"{family.adapter.BYTES_MODEL}.py")
    cfg = family.adapter.program_config(conf)
    # the generator's and the window's vocabulary is the file's
    assert cfg.vocab == conf["vocab_size"]
    assert family.bytes_model.decode_step_bytes(conf, 100) > 0


def plain_files(kind):
    return sorted(f for f in os.listdir(os.path.join(BENCH, kind))
                  if f.endswith(".py"))


@pytest.mark.parametrize("name", plain_files("references"))
def test_a_reference_imports_nothing_of_the_program(name):
    with open(os.path.join(BENCH, "references", name)) as f:
        src = f.read()
    code = [ln.split("#")[0] for ln in src.splitlines()]
    assert not any(re.search(r"\b(import|from)\b.*oncilla_tpu", ln)
                   for ln in code), name
    assert "importlib" not in src and "__import__" not in src


def test_only_the_adapters_import_a_model_module():
    """``harness.py`` names no model; under ``benchmark/`` a model module of
    the program is imported by the families' adapters (and by the warmers
    and ``census.py``, which drive the program's own entry points)."""
    pat = re.compile(r"oncilla_tpu\.models|LlamaConfig|\bllama\b")
    with open(os.path.join(BENCH, "harness.py")) as f:
        assert not pat.search(f.read())
    hits = set()
    for dirpath, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    code = "\n".join(ln for ln in f.read().splitlines()
                                     if re.search(r"\b(import|from)\b", ln))
                if re.search(r"oncilla_tpu\.models", code):
                    hits.add(os.path.relpath(os.path.join(dirpath, fn), BENCH))
    assert hits <= {"families/dense_gqa.py", "warmers/paged_dense.py",
                    "census.py"}, hits
    assert "families/dense_gqa.py" in hits


# -- a temporary copy with a dummy of everything, and the tick loop on it -----------

TINY_CONFIG = {
    "name": "tiny", "source": "tests", "family": "tiny_family",
    "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 128,
    "vocab_size": 256, "max_position_embeddings": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "torch_dtype": "float32", "sliding_window": None,
    "reduced": [], "assumed": {}, "guarantees": {"cold_replicas": 2},
    "tolerance": {"max_abs_dlogit": 1e-3, "argmax_share": 1.0,
                  "why": "float32 on the CPU: the paged path and the plain "
                         "forward differ by summation order alone"},
    "tolerance_served": {"max_logit_gap": 1e-3, "why": "as tolerance"},
}
TINY_TRAFFIC = {
    "generator": "dummy_gen",
    "why": "4 clients on 2 seats at the tiny size", "who": "tests",
    "params": {"clients": 4, "arrivals": {"kind": "closed"},
               "shared_prefix_tokens": 0,
               "prompt": {"median": 20, "sigma": 0.5, "min": 9, "max": 38},
               "new_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 12},
               "avoid_multiple_of": 8, "pool": 12, "shape_seed": 1},
    "engine": {"page_tokens": 8, "max_active": 4, "max_batch": 2,
               "prefix_cache": False, "prefetch_workers": 2, "hot_pages": 8,
               "warm_pages": 2, "cold_pages": 64, "cold_daemons": 3},
    "warm": {"warmer": "paged_dense", "prefill_context_pages": 5,
             "fused_buckets": [[2, 4, 8]], "ramp": [[1, 1], [2, 1]],
             "requests": 6},
    "expect": {"window_hops_nonzero": ["hbm>host", "host>remote"]},
}


def snapshot(root) -> dict:
    """Every file under ``root``/benchmark with its bytes."""
    out = {}
    for dirpath, _, files in os.walk(root / "benchmark"):
        for fn in files:
            path = os.path.join(dirpath, fn)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    """A copy of the benchmark with a cell, a configuration, a model family
    (its adapter, its reference and its bytes model, under names of their
    own), a mix, a generator and a per-layer metric ADDED as files and
    entries of their own: nothing that was there is edited."""
    tmp = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH, tmp / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    before = snapshot(tmp)
    adapter = (tmp / "benchmark/families/dense_gqa.py").read_text()
    (tmp / "benchmark/families/tiny_family.py").write_text(
        adapter.replace('REFERENCE = "dense_gqa"', 'REFERENCE = "tiny_ref"')
        .replace('BYTES_MODEL = "dense_gqa"', 'BYTES_MODEL = "tiny_bytes"')
        .replace('"paged_decode_batch_step"', '"tiny_step_program"'))
    shutil.copy(tmp / "benchmark/references/dense_gqa.py",
                tmp / "benchmark/references/tiny_ref.py")
    (tmp / "benchmark/bytes_models/tiny_bytes.py").write_text(
        "def decode_step_bytes(conf, context_tokens):\n"
        "    return 1000 * conf['hidden_size'] + context_tokens\n")
    (tmp / "benchmark/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (tmp / "benchmark/traffic/tiny-mix.json").write_text(json.dumps(TINY_TRAFFIC))
    shutil.copy(tmp / "benchmark/generators/lognormal_turns.py",
                tmp / "benchmark/generators/dummy_gen.py")
    (tmp / "benchmark/layer_metrics/dummy.ticks.py").write_text(
        "def read(stats, spans, trace, cell):\n"
        "    return cell['window']['ticks']\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny", "source": "tests", "reduced": [],
                         "file": "benchmark/configs/tiny.json", "why": "tests"})
    b["workloads"].append({"name": "tiny.tiny-mix", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1, "why": "tests"})
    b["per_layer"].append({"name": "dummy.ticks", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "dummy", "moves": "out_tok_s",
                           "workloads": ["tiny.tiny-mix"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    after = snapshot(tmp)
    assert {k: after[k] for k in before} == before   # nothing edited
    assert len(after) == len(before) + 7
    return tmp


@pytest.fixture(scope="module")
def tiny_harness(tiny_copy):
    return load(str(tiny_copy / "benchmark/harness.py"), "bench_harness_tiny_copy")


def test_added_files_and_entries_resolve_in_a_copy(tiny_harness, tiny_copy):
    cell = tiny_harness.load_cell("tiny.tiny-mix")
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["generator"] == "dummy_gen"
    assert "dummy.ticks" in [m["name"] for m in cell.per_layer]
    # a metric limited to other cells is not this cell's
    assert "prefix.reused_share" not in [m["name"] for m in cell.per_layer]
    gen = tiny_harness.load_plugin("generators", "dummy_gen")
    assert gen.__file__.startswith(str(tiny_copy))
    read = tiny_harness.load_plugin("layer_metrics", "dummy.ticks").read
    assert read({}, {}, None, {"window": {"ticks": 7}}) == 7
    # the added family is found through the configuration's key, and brings
    # its own reference, bytes model and program name
    family = tiny_harness.load_family(cell.config)
    assert family.name == "tiny_family"
    for mod, rel in ((family.adapter, "families/tiny_family.py"),
                     (family.reference, "references/tiny_ref.py"),
                     (family.bytes_model, "bytes_models/tiny_bytes.py")):
        assert mod.__file__ == str(tiny_copy / "benchmark" / rel)
    assert family.adapter.DECODE_STEP_PROGRAM == "tiny_step_program"
    assert family.bytes_model.decode_step_bytes(cell.config, 7) == 64007
    # and the cells that were there still resolve, untouched
    old = tiny_harness.load_cell("internlm2-1.8b.agent-shared")
    assert old.chips == 1
    assert tiny_harness.load_family(old.config).name == "dense_gqa"


def test_step_readers_take_program_and_bytes_from_the_family(tiny_harness):
    """``step.device_ms`` and ``step.roofline_share`` hold no program name
    and no bytes of their own: the added family's reach them."""
    cell = tiny_harness.load_cell("tiny.tiny-mix")
    family = tiny_harness.load_family(cell.config)
    tr = tiny_harness.load_plugin("", "trace_reduce")
    trace = {"programs": {"tiny_step_program": {"count": 4, "total_s": 0.02},
                          "paged_decode_batch_step": {"count": 9, "total_s": 9.0}}}
    info = {"config": cell.config, "window": {"context_tokens": 2000},
            "peak": {"hbm_bytes_per_s": 1e9},
            "lib": {"trace_reduce": tr, "family": family.adapter,
                    "bytes_model": family.bytes_model}}
    stats = {"batch": {"steps": 10}}
    read = lambda name: tiny_harness.load_plugin("layer_metrics", name).read(  # noqa: E731
        stats, {}, trace, info)
    assert read("step.device_ms") == pytest.approx(5.0)
    # least time (64000 + 200) B / 1e9 B/s over 5 ms
    assert read("step.roofline_share") == pytest.approx(
        100 * 64200e-9 / 5e-3)
    trace["programs"].pop("tiny_step_program")
    assert read("step.device_ms") is None
    assert read("step.roofline_share") is None


def run_tiny(tiny_harness, seed: int) -> dict:
    """One whole run of the tiny cell, all of ``run_cell`` but its look for a
    chip (``platform="cpu"``)."""
    import time

    import jax

    # run_cell turns the persistent compile cache on for its process; the
    # tests that follow in this worker must not inherit that.
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        return tiny_harness.run_cell(
            "tiny.tiny-mix", seed=seed, seconds=2.0, trace=False,
            t_start=time.perf_counter(), platform="cpu")
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


@pytest.fixture(scope="module")
def tiny_line(tiny_harness):
    return run_tiny(tiny_harness, 2**31 + 5)


def test_tick_loop_returns_every_token_and_the_contracts_line(tiny_line):
    line = json.loads(json.dumps(tiny_line))   # what run.py prints
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_s", "ttft_ms_p90", "itl_ms_p95", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"   # and so never a measurement


def test_the_line_ends_with_each_number_compared_beside_its_limit(tiny_line):
    assert list(tiny_line)[-1] == "compared"
    c = tiny_line["compared"]
    assert set(c) == {"max_abs_dlogit", "argmax_share", "tokens_compared",
                      "served_logit_gap", "served_tokens", "requests_failed", "requests_attempted",
                      "hops.hbm>host", "hops.host>remote"}
    tol = TINY_CONFIG["tolerance"]
    assert c["max_abs_dlogit"]["limit"] == tol["max_abs_dlogit"]
    assert 0 < c["max_abs_dlogit"]["value"] <= tol["max_abs_dlogit"]
    assert c["argmax_share"] == {"value": 1.0, "want": ">=", "limit": 1.0}
    assert c["tokens_compared"]["value"] >= 8
    # the window's own requests: some tens of served tokens at this size
    assert c["served_logit_gap"]["limit"] == 1e-3
    assert 0 <= c["served_logit_gap"]["value"] <= 1e-3
    assert c["served_tokens"]["value"] >= 8
    assert c["requests_failed"] == {"value": 0, "want": "<=", "limit": 0}
    assert c["hops.hbm>host"]["value"] >= 1
    for entry in c.values():
        ok = (entry["value"] <= entry["limit"] if entry["want"] == "<="
              else entry["value"] >= entry["limit"])
        assert ok, c


def test_a_token_altered_where_it_is_made_is_not_correct(tiny_harness,
                                                        monkeypatch):
    """The timed path broken underneath a whole run: the fused step hands
    back its logits shifted by one id, so every decoded token is another
    one. ``correct`` comes out false, through the numbers compared."""
    import jax.numpy as jnp

    import oncilla_tpu.serving.engine as engine_mod

    fused = engine_mod.paged_decode_batch_step_jit

    def shifted(*args):
        logits, tail_k, tail_v = fused(*args)
        return jnp.roll(logits, 1, axis=-1), tail_k, tail_v

    monkeypatch.setattr(engine_mod, "paged_decode_batch_step_jit", shifted)
    line = run_tiny(tiny_harness, 2**31 + 6)
    assert line["correct"] is False
    c = line["compared"]
    assert c["max_abs_dlogit"]["value"] > 100 * c["max_abs_dlogit"]["limit"]
    assert c["argmax_share"]["value"] < c["argmax_share"]["limit"]
    # and what the window itself served is far from the reference's best
    assert c["served_logit_gap"]["value"] > 100 * c["served_logit_gap"]["limit"]
    # every request still came back whole: the fault is in what they say
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_one_precision_lower_is_not_correct(seed):
    """``control.py`` at a size a test can hold: the reference over weights
    rounded to bfloat16, put in the place of the float32 tiny model, fails
    the tiny configuration's tolerance (by the widest logit gap; the
    arg-maxes of 40 positions survive bfloat16)."""
    control = load(os.path.join(BENCH, "control.py"), "bench_control_under_test")
    conf = dict(TINY_CONFIG, family="dense_gqa")
    out = control.control(conf, seed, tokens=40)
    assert out["lower"] == "bfloat16" and out["tokens_compared"] == 40
    assert out["correct"] is False
    d = out["max_abs_dlogit"]
    assert d["limit"] == 1e-3 and d["value"] > 3 * d["limit"]
    assert out["served_logit_gap"]["limit"] == 1e-3
    # and the same comparison lets the unrounded weights through
    control.LOWER["float32"] = ("float32", 8, 23)
    try:
        same = control.control(conf, seed, tokens=40)
    finally:
        control.LOWER["float32"] = ("bfloat16", 8, 7)
    assert same["correct"] is True and same["max_abs_dlogit"]["value"] == 0


def test_readers_on_counters_of_the_tiny_run(harness):
    """The counter- and span-fed readers, on hand-made window deltas."""
    cell = {"window": {"tokens": 200, "ticks": 50, "compiles": 0,
                       "prompt_tokens": 1000, "reused_tokens": 850},
            "traffic": {"engine": {"max_batch": 8, "prefix_cache": True}},
            "page_bytes": 3 << 20}
    stats = {"batch": {"steps": 40, "size_sum": 240}, "stall_s": 0.5,
             "moves": {"promote": 10, "demote": 30}}
    spans = {"serve_batch_step": {"count": 40, "total_s": 2.0},
             "put": {"count": 5, "total_s": 0.1}, "get": {"count": 5, "total_s": 0.15},
             "alloc": {"count": 9, "total_s": 9.0}}
    read = lambda name: harness.load_plugin("layer_metrics", name).read(  # noqa: E731
        stats, spans, None, cell)
    assert read("entry.window_compiles") == 0
    assert read("sched.tick_ms") == pytest.approx(50.0)
    assert read("sched.batch_fill") == pytest.approx(75.0)
    assert read("prefix.reused_share") == pytest.approx(85.0)
    assert read("tiers.stall_ms_per_tok") == pytest.approx(2.5)
    assert read("tiers.moved_MiB_per_tok") == pytest.approx(40 * 3 / 200)
    assert read("memplane.op_ms_per_tick") == pytest.approx(5.0)
    # nothing to read: nothing returned
    for name in ("step.device_ms", "step.roofline_share", "dma.roofline_share",
                 "device.idle_share"):
        assert read(name) is None
    cell["traffic"]["engine"]["prefix_cache"] = False
    assert read("prefix.reused_share") is None


def test_census_counts_the_shapes_a_mix_reaches():
    """The tool a mix's ``warm`` lists are written from: on the committed
    agent-shared mix every prefill context it finds is one the mix warms."""
    census = load(os.path.join(BENCH, "census.py"), "bench_census_under_test")
    out = census.census("agent-shared", [3], requests=6)
    with open(os.path.join(BENCH, "traffic", "agent-shared.json")) as f:
        warm = json.load(f)["warm"]
    assert out["fused_buckets"] and out["by_seed"][3]["ticks"] > 0
    assert max(c for c, _ in out["prefill_context_pages"]) < warm["prefill_context_pages"]
    assert all(b[0] in (1, 2, 4, 8) and b[1] in (16, 32)
               for b, _ in out["fused_buckets"])


def test_run_py_refuses_to_measure_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "internlm2-1.8b.agent-shared", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "refused" in out.stderr
