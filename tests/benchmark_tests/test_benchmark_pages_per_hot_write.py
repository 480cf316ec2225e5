"""CPU tests of ``benchmark/layer_metrics/tiers.pages_per_hot_write.py``:
the ratio on hand-made counters, silence where the program has no
``hot_writes`` counters (a parent that lacks them, or a family that never
writes a group), the reader on what a store really counts over a window,
and the entry in ``BENCHMARK.json`` found BY NAME at the end. A CPU run
proves names and arithmetic, never a time."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "tiers.pages_per_hot_write"
CELLS = ["laguna-s-2.1-ep4-d5.mixed-lengths",
         "mellum2-12b-a2.5b-d8.file-context"]


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location(
        "bench_harness_hot_writes",
        os.path.join(ROOT, "benchmark", "harness.py"))
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
    # chunks of eight pages, a write a kind a chunk
    ({"hot_writes": {"pages": 64, "dispatches": 8}}, 8.0),
    # a last chunk of fewer pages a prompt
    ({"hot_writes": {"pages": 61, "dispatches": 9}}, 61 / 9),
    # no group written in the window, or a program without the counters
    ({"hot_writes": {"pages": 0, "dispatches": 0}}, None),
    ({"places": {"pages": 40, "walks": 0}}, None),
    ({}, None),
])
def test_pages_over_dispatches_with_and_without_the_counters(read, stats,
                                                            want):
    got = read(stats, {}, None, {})
    assert got == (pytest.approx(want) if want is not None else None)


def test_reads_what_a_store_counts_over_a_window(harness, read):
    """A store's counters as the harness takes them: a snapshot before and
    one after, the difference read; pages placed one at a time count
    nowhere."""
    import jax.numpy as jnp

    import oncilla_tpu as ocm
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.tiers import TieredPageStore

    pb = 4096
    ctx = ocm.Ocm(config=ocm.OcmConfig(host_arena_bytes=1 << 20,
                                       device_arena_bytes=64 * pb))
    store = TieredPageStore(ctx, pb, hot_capacity=60,
                            stats=ServingStats("hot_writes"))
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(1, 256, (8, pb), dtype=np.uint8))
    try:
        store.alloc_page(rows[0])
        assert read(store.stats.snapshot(), {}, None, {}) is None
        store.alloc_pages(rows, 8)
        before = store.stats.snapshot()
        for count in (8, 8, 3):
            store.alloc_pages(rows, count)
        store.alloc_page(rows[1])
        win = harness.delta(store.stats.snapshot(), before)
        assert win["hot_writes"] == {"pages": 19, "dispatches": 3}
        assert read(win, {}, None, {}) == pytest.approx(19 / 3)
    finally:
        store.close()
        ctx.tini()


def test_the_entry_is_found_by_name_at_the_end(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == names.index("prefill.pages_per_program") + 1
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter",
                     "layer": "tier store (serving/tiers.py)",
                     "moves": "out_tok_s", "workloads": CELLS}
    # the layer the tier store's other metrics have, letter for letter
    (walks,) = [m for m in bench["per_layer"]
                if m["name"] == "tiers.walks_per_page_placed"]
    assert entry["layer"] == walks["layer"]
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       f"{NAME}.py"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_listed_cells_traced_run_asks_the_reader(harness, cell):
    assert NAME in [m["name"] for m in harness.load_cell(cell).per_layer]
