"""CPU tests of ``benchmark/layer_metrics/prefill.pages_per_program.py``:
the ratio on hand-made counters, silence where the program has no
``prefill.pages`` counter (a parent that lacks it) or ran no page program,
the reader on what ``ServingStats`` counts over a window, and the entry in
``BENCHMARK.json`` found BY NAME behind the accepted entries. A CPU run
proves names and arithmetic, never a time."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "prefill.pages_per_program"
CELLS = ["laguna-s-2.1-ep4-d5.mixed-lengths",
         "mellum2-12b-a2.5b-d8.file-context"]


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_pages", os.path.join(ROOT, "benchmark", "harness.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def read(harness):
    return harness.load_plugin("layer_metrics", NAME).read


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("stats, want", [
    # a page a program: every family that states no chunk_pages
    ({"prefill": {"pages": 40}, "batch": {"prefill_chunks": 40}}, 1.0),
    # chunks of eight, and a last chunk of fewer pages a prompt
    ({"prefill": {"pages": 64}, "batch": {"prefill_chunks": 8}}, 8.0),
    ({"prefill": {"pages": 65}, "batch": {"prefill_chunks": 9}}, 65 / 9),
    # no page program in the window, or a program without the counter
    ({"prefill": {"pages": 0}, "batch": {"prefill_chunks": 0}}, None),
    ({"batch": {"prefill_chunks": 12}}, None),
    ({"prefill": {}, "batch": {"prefill_chunks": 12}}, None),
    ({}, None),
])
def test_pages_over_programs_with_and_without_the_counter(read, stats, want):
    got = read(stats, {}, None, {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_reads_what_the_stats_count_over_a_window(harness, read):
    """The counters as the harness takes them: a snapshot before and one
    after, the difference read."""
    from oncilla_tpu.serving.metrics import ServingStats

    stats = ServingStats("pages")
    assert read(stats.snapshot(), {}, None, {}) is None
    stats.note_prefill_chunk()
    before = stats.snapshot()
    for pages in (8, 8, 8, 3, 1):
        stats.note_prefill_chunk(pages)
    win = harness.delta(stats.snapshot(), before)
    assert win["prefill"] == {"pages": 28}
    assert win["batch"]["prefill_chunks"] == 5
    assert read(win, {}, None, {}) == pytest.approx(28 / 5)


def test_the_entry_is_found_by_name_at_the_end(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == names.index("moe.page_touched_share") + 1
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter",
                     "layer": "model steps (models/kv_paging.py)",
                     "moves": "ttft_ms_p90", "workloads": CELLS}
    # the layer `prefill.chunk_wall_ms` has, letter for letter
    (chunk,) = [m for m in bench["per_layer"]
                if m["name"] == "prefill.chunk_wall_ms"]
    assert entry["layer"] == chunk["layer"]
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       f"{NAME}.py"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_listed_cells_traced_run_asks_the_reader(harness, cell):
    assert NAME in [m["name"] for m in harness.load_cell(cell).per_layer]
