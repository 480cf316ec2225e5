"""The serving engine's tick — fused per-tick decode, chunked prefill,
admission-aware scheduling.

The correctness gate is the dense family's plain reference
(``benchmark/references/dense_gqa.py``, float32, nothing imported from
the program): the logits every served token was picked from agree with
the reference's, teacher-forced on what the session read and wrote
(:func:`held_to_reference`). Fusing sessions into one padded jit call,
chunked prefill, priority seating, tier churn and budget-degraded faults
are all scheduling/storage effects and must never change a single
emitted token. CPU-only (conftest pins the backend); cluster-backed
chaos legs live in ``python -m oncilla_tpu.serving --smoke``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import os
import weakref

import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.prefix import PrefixCache
from oncilla_tpu.serving.tiers import Tier, TieredPageStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = 8  # page_tokens for every engine in this file
# The published keys the reference's ``dims_of`` reads, as
# ``LlamaConfig.tiny()`` has them.
TINY_CONF = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "rope_theta": 1e4, "rms_norm_eps": 1e-5}


@functools.cache
def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_dense_gqa",
        os.path.join(ROOT, "benchmark", "references", "dense_gqa.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_model():
    from oncilla_tpu.models import LlamaConfig, init_params_host

    cfg = LlamaConfig.tiny()
    return cfg, init_params_host(0, cfg)


def build_engine(tiny_model, *, share=True, hot=3, warm=4, prefetch=0,
                 max_active=4, max_batch=None, step_budget_ms=None,
                 name="t", **engine_kw):
    """An engine that keeps the logits its tokens were picked from."""
    from oncilla_tpu.serving.engine import ServingEngine

    cfg, params = tiny_model
    pb = ServingEngine.page_nbytes(cfg, P)
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=1 << 20,
    ))
    store = TieredPageStore(ctx, pb, hot_capacity=hot, warm_capacity=warm,
                            stats=ServingStats(name))
    prefix = PrefixCache(store, P) if share else None
    try:
        eng = ServingEngine(params, cfg, store, prefix, page_tokens=P,
                            max_active=max_active,
                            prefetch_workers=prefetch, name=name,
                            max_batch=max_batch, keep_logits=True,
                            step_budget_ms=step_budget_ms, **engine_kw)
    except BaseException:
        store.close()
        ctx.tini()
        raise
    return ctx, store, eng


def held_to_reference(tiny_model, prompts, results):
    """Hold served sessions (``SessionResult`` of tenant ``t<i>`` for
    ``prompts[i]``, from an engine that keeps logits) to the plain
    reference: every emitted token is the arg-max of the logits kept for
    it, and those logits are the reference's, teacher-forced on
    ``prompt + out[:-1]``, at the rows that emitted. Returns the tokens by
    tenant."""
    cfg, params = tiny_model
    ref = load_reference()
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.rope_theta, cfg.norm_eps,
            cfg.window) == ref.dims_of(TINY_CONF)
    results = list(results)
    assert results
    for res in results:
        prompt = [int(t) for t in prompts[int(res.tenant[1:])]]
        out = list(res.out_tokens)
        got = np.stack(res.out_logits)
        assert len(out) == len(got) > 0 and (got.argmax(-1) == out).all()
        seq = np.asarray([prompt + out[:-1]], np.int32)
        rows = np.arange(len(prompt) - 1, seq.shape[1])
        want = ref.logits_at(params, seq, rows, TINY_CONF)[0]
        assert np.abs(want).max() > 0.1
        np.testing.assert_allclose(got, want, atol=1e-4,
                                   err_msg=res.tenant)
    return {r.tenant: list(r.out_tokens) for r in results}


def run_prompts(tiny_model, prompts, *, new_tokens=6, priorities=None,
                watch=None, **kw):
    """Serve ``prompts`` to the end and hold every session to the plain
    reference (:func:`held_to_reference`). ``new_tokens`` is one budget or
    one a prompt; ``watch`` is handed the engine before anything is
    submitted."""
    from oncilla_tpu.serving.engine import Request

    if isinstance(new_tokens, int):
        new_tokens = [new_tokens] * len(prompts)
    ctx, store, eng = build_engine(tiny_model, **kw)
    try:
        if watch is not None:
            watch(eng)
        for i, p in enumerate(prompts):
            req = Request(tenant=f"t{i}", tokens=list(p),
                          max_new_tokens=new_tokens[i])
            if priorities is not None:
                req.priority = priorities[i]
            eng.submit(req)
        results = eng.run()
        order = [r.tenant for r in results]
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    assert sorted(order) == sorted(f"t{i}" for i in range(len(prompts)))
    return held_to_reference(tiny_model, prompts, results), meta, order


def seeded_prompts(cfg, seed, *, n=4, shared=20, suffix=4):
    """Workload with a shared prefix, one identical pair (t0/t1), and
    per-tenant suffixes. ``shared + suffix`` page-aligned makes the
    pair's last page land in the CoW partial-adoption branch (the
    laggard adopts all-but-one token of the leader's final page by
    copy-on-write)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, cfg.vocab, shared).tolist()
    p0 = base + rng.integers(1, cfg.vocab, suffix).tolist()
    prompts = [p0, list(p0)]
    for _ in range(n - 2):
        prompts.append(base + rng.integers(1, cfg.vocab, suffix).tolist())
    return prompts


# -- 0. the gate itself, and the one scheduler --------------------------------


def test_reference_check_fails_on_a_row_from_another_session(tiny_model):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (P + 3, 5)]
    ctx, store, eng = build_engine(tiny_model, share=False, hot=8, warm=4)
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=4))
        t0, t1 = sorted(eng.run(), key=lambda r: r.tenant)
    finally:
        eng.close()
        store.close()
        ctx.tini()
    held_to_reference(tiny_model, prompts, [t0, t1])
    # t0's last token picked from t1's last row: still the arg-max of the
    # row kept for it, and teacher-forcing never reads a last token, so only
    # the comparison with the reference's logits can tell.
    row = t1.out_logits[-1]
    forged = dataclasses.replace(
        t0, out_tokens=t0.out_tokens[:-1] + [int(row.argmax())],
        out_logits=t0.out_logits[:-1] + [row])
    with pytest.raises(AssertionError, match="t0"):
        held_to_reference(tiny_model, prompts, [forged, t1])


@pytest.mark.parametrize("family", ["dense", "latent"])
def test_there_is_one_scheduler_whatever_is_asked_for(
        tiny_model, monkeypatch, family):
    from oncilla_tpu.serving.engine import Request

    model = tiny_model
    if family == "latent":
        import jax

        from oncilla_tpu.models import latent_moe as lm

        cfg = lm.LatentMoeConfig.tiny()
        model = cfg, lm.init_params(jax.random.key(3), cfg)
    cfg, _ = model
    # The keyword is a word the benchmark's callers still pass; its other
    # value names a loop that is gone.
    with pytest.raises(ValueError, match="interleaved loop is gone"):
        build_engine(model, batched=False)
    # A stray switch of that loop in the environment changes nothing.
    monkeypatch.setenv("OCM_SERVING_BATCH", "0")
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in (P + 2, 3)]
    ctx, store, eng = build_engine(model, share=False, hot=8, warm=4)
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=3))
        results = eng.run()
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    assert sorted(len(r.out_tokens) for r in results) == [3, 3]
    assert meta["batch"]["steps"] > 0 and meta["batch"]["size_max"] == 2
    assert meta["batch"]["prefill_chunks"] == 1


# -- 1. the reference's tokens through tier churn + CoW adoption ------------


class SeatWatch:
    """Watch an engine's tail stack. After every seating: the seats are the
    batch, contiguous from 0, each session in the seat it says it has and
    holding no tail of its own. Logs the tenants by seat a step, the widths
    a stack was made at, and the seat programs dispatched in earnest (those
    ``_new_tails`` runs on scratch to have them built are not counted);
    ``built`` collects the programs' cache sizes around every new stack."""

    PROGRAMS = ("_seat_write_jit", "_seat_move_jit", "_seat_read_jit")

    def __init__(self, monkeypatch):
        import oncilla_tpu.serving.engine as engine_mod

        self.steps: list[list[str]] = []
        self.widths: list[int] = []
        self.calls = dict.fromkeys(self.PROGRAMS, 0)
        self.built: list[tuple] = []
        self._warming = False
        self._real = {n: getattr(engine_mod, n) for n in self.PROGRAMS}
        for name in self.PROGRAMS:
            monkeypatch.setattr(engine_mod, name, self._counted(name))

    def _counted(self, name):
        def call(*args):
            self.calls[name] += not self._warming
            return self._real[name](*args)
        return call

    def cache_sizes(self) -> tuple:
        return tuple(self._real[n]._cache_size() for n in self.PROGRAMS)

    def __call__(self, eng):
        seat_batch, new_tails = eng._seat_batch, eng._new_tails

        def seated(batch):
            changes = seat_batch(batch)
            seats = eng._seats
            assert len(seats) == len(batch) and None not in seats
            assert {id(s) for s in seats} == {id(s) for s in batch}
            assert [s.seat for s in seats] == list(range(len(seats)))
            assert all(s.tails is None for s in seats)
            assert eng._tails[0].shape[1] >= len(seats)
            self.steps.append([s.req.tenant for s in seats])
            return changes

        def made(b_pad):
            before = self.cache_sizes()
            self._warming = True
            try:
                new_tails(b_pad)
            finally:
                self._warming = False
            self.widths.append(b_pad)
            self.built.append((before, self.cache_sizes()))

        eng._seat_batch, eng._new_tails = seated, made

    def left_from_the_middle(self) -> bool:
        """Some step lost the tenant of a seat that was not the last while
        the tenant of the last seat stayed."""
        return any(
            t not in cur and prev[-1] in cur
            for prev, cur in zip(self.steps, self.steps[1:])
            for t in prev[:-1])


@pytest.mark.parametrize("case", [
    "churn-and-cow", "more-admitted-than-seats", "a-middle-seat-finishes"])
def test_fused_step_serves_the_reference_through_churn_and_cow(
        tiny_model, monkeypatch, case):
    cfg, _ = tiny_model
    prompts = seeded_prompts(cfg, 11, n=5, shared=20, suffix=4)
    # hot=2/warm=2 with 5 multi-page sessions forces continuous
    # demotion to the cold stand-in and promotion back (tier churn);
    # outputs must not notice.
    kw = dict(share=True, hot=2, warm=2, new_tokens=8, max_active=4)
    if case == "more-admitted-than-seats":
        kw.update(max_batch=2)
    elif case == "a-middle-seat-finishes":
        # t2 is through long before its neighbours.
        kw.update(new_tokens=[8, 8, 2, 8, 8])
    # One session at a time through the fused step at B=1: no neighbour
    # in the batch, nobody to share with while it runs.
    outs_alone, meta_alone, _ = run_prompts(
        tiny_model, prompts, **{**kw, "max_active": 1, "max_batch": 1})
    assert meta_alone["batch"]["size_max"] == 1
    watch = SeatWatch(monkeypatch)
    # Both runs are held to the plain reference (run_prompts) ...
    outs_b, meta_b, _ = run_prompts(tiny_model, prompts, watch=watch, **kw)
    # ... and a session's tokens do not depend on who sits beside it.
    assert outs_b == outs_alone
    # Identical prompts emitted identical continuations.
    assert outs_b["t0"] == outs_b["t1"]
    # Every step's rows were its seats, contiguous (SeatWatch), and the
    # counters are those steps' seats.
    assert len(watch.steps) == meta_b["batch"]["steps"]
    tails = meta_b["tails"]
    assert (tails["seats_kept"] + tails["seats_written"]
            == meta_b["batch"]["size_sum"])
    assert tails["seats_kept"] > 0
    if case == "more-admitted-than-seats":
        assert meta_b["batch"]["size_max"] == 2
        assert meta_b["preempts"].get("slot", 0) > 0
    elif case == "a-middle-seat-finishes":
        assert watch.left_from_the_middle()
    # The fused path actually ran (not a degenerate batch of one).
    assert meta_b["batch"]["steps"] > 0
    assert meta_b["batch"]["size_max"] >= 2
    # Tier churn engaged beside the neighbours...
    assert meta_b["moves"]["demote"] > 0
    assert meta_b["moves"]["promote"] > 0
    # ...and so did prefix sharing with a CoW partial adoption
    # (the t0/t1 identical pair).
    assert meta_b["prefix"]["hits"] > 0
    assert meta_b["prefix"]["cow"] >= 1


# -- 2. chunked prefill ----------------------------------------------------


def test_chunked_prefill_admits_long_prompt_in_slices(tiny_model):
    cfg, _ = tiny_model
    rng = np.random.default_rng(23)
    long = rng.integers(1, cfg.vocab, 6 * P).tolist()  # 6-page prompt
    shorts = [rng.integers(1, cfg.vocab, 5).tolist() for _ in range(3)]
    prompts = [long] + shorts
    kw = dict(share=False, hot=6, warm=8, new_tokens=10, max_active=4)
    # Held to the plain reference: a page of prompt through the page
    # program, and its tokens one by one, give the reference's logits.
    outs_b, meta_b, _ = run_prompts(tiny_model, prompts, **kw)
    assert [len(outs_b[f"t{i}"]) for i in range(4)] == [10] * 4
    b = meta_b["batch"]
    # The 6-page prompt admitted one page-sized slice per tick.
    assert b["prefill_chunks"] >= 6
    # The batch never stalled behind it: the short sessions kept
    # decoding every tick, so fused steps at least cover their decode
    # tokens and ran concurrently with the chunking ticks.
    assert b["steps"] >= kw["new_tokens"]
    assert b["size_max"] >= 2
    # Prefill tokens accounted exactly once each (chunked or through the
    # step): every prompt token teacher-forced once.
    assert meta_b["tokens"]["prefill"] == sum(len(p) for p in prompts)


# -- 3. admission-aware scheduler ------------------------------------------


def test_scheduler_prio_high_admitted_and_seated_first(tiny_model):
    from oncilla_tpu.qos.policy import PRIO_HIGH, PRIO_NORMAL

    cfg, _ = tiny_model
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, cfg.vocab, 6).tolist() for _ in range(4)]
    # The PRIO_HIGH request is submitted LAST but must be admitted (and
    # seated) first; max_batch=2 < max_active=4 forces slot contention
    # every tick, which the scheduler must resolve by priority.
    prios = [PRIO_NORMAL, PRIO_NORMAL, PRIO_NORMAL, PRIO_HIGH]
    kw = dict(share=False, new_tokens=6, max_active=4, max_batch=2)
    # Priority is a scheduling effect only: run_prompts holds every
    # session's logits to the plain reference.
    outs_b, meta_b, order = run_prompts(tiny_model, prompts,
                                        priorities=prios, **kw)
    assert order[0] == "t3"  # the PRIO_HIGH tenant finished first
    assert meta_b["preempts"].get("slot", 0) >= 1
    assert all(len(o) == 6 for o in outs_b.values())


def test_scheduler_expired_budget_degrades_to_stall(tiny_model):
    import concurrent.futures as cf

    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(37)
    prompt = rng.integers(1, cfg.vocab, 2 * P).tolist()
    ctx, store, eng = build_engine(tiny_model, share=False, hot=4, warm=4,
                                  prefetch=2, step_budget_ms=20)
    try:
        eng.submit(Request(tenant="t0", tokens=list(prompt),
                           max_new_tokens=4))
        # Prefill the prompt's two pages.
        while not eng.active or any(
                eng._bulk_prefill(s) for s in eng.active):
            eng._tick()
        sess = eng.active[0]
        page = sess.entries[0].page
        store.demote(page, Tier.WARM)
        # A prefetch that never lands: the next step's wait must expire
        # at the step budget and degrade to a synchronous fault with
        # the wait recorded as stall — never a wedged batch.
        eng.prefetcher._futures[page.page_id] = cf.Future()
        stalls0 = eng.stats.stalls
        eng._tick()
        assert eng.stats.stalls > stalls0
        assert eng.stats.stall_s > 0
        # The preempt ledger recorded the yielded seat before the
        # forced (budget-bounded) fault seated it anyway.
        assert eng.stats.preempts.get("cold_page", 0) >= 1
        results = eng.run()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    # Degradation is accounting-only: the logits are the reference's.
    outs = held_to_reference(tiny_model, [prompt], results)
    assert len(outs["t0"]) == 4


# -- 4. jit recompilations bounded by shape buckets ------------------------


def test_batched_recompilations_bounded_by_shape_buckets(tiny_model):
    from oncilla_tpu.models import paged_decode_batch_step_jit as kern

    cfg, _ = tiny_model
    rng = np.random.default_rng(41)
    # Heterogeneous batch sizes (1..5 live sessions as tenants finish)
    # and context lengths (1..4 pages) — hundreds of tokens through
    # the fused kernel.
    prompts = [rng.integers(1, cfg.vocab, ln).tolist()
               for ln in (5, 9, 17, 25, 30)]

    def workload():
        return run_prompts(tiny_model, prompts, new_tokens=12,
                           share=False, hot=8, warm=8, max_active=5)

    before = kern._cache_size()
    outs, meta, _ = workload()
    first = kern._cache_size() - before
    tokens = sum(len(o) for o in outs.values()) \
        + meta["tokens"]["prefill"]
    # Shape-bucketed padding keeps compiles O(log batch * log pages):
    # B buckets {1,2,4,8} x page buckets {1,2,4} — nowhere near the
    # token count.
    assert meta["batch"]["steps"] > 0
    assert 0 < first <= 8
    assert first < tokens / 10
    # A second identical workload hits the jit cache exactly.
    outs2, _, _ = workload()
    assert kern._cache_size() - before == first
    assert outs2 == outs


# -- 5. tick anatomy: one span tree, every name under one parent ------------

# docs/OBSERVABILITY.md, "Serving tick anatomy": span -> its one parent.
TICK_SPANS = {
    "tick": None,
    "tick.admit": "tick",
    "tick.match": "tick",
    "serve_prefill_chunk": "tick",
    "prefill.match": "serve_prefill_chunk",
    "prefill.residency": "serve_prefill_chunk",
    "prefill.dispatch": "serve_prefill_chunk",
    "prefill.sync": "serve_prefill_chunk",
    "prefill.ship": "serve_prefill_chunk",
    "tick.select": "tick",
    "serve_batch_step": "tick",
    "step.residency": "serve_batch_step",
    "step.pool": "serve_batch_step",
    "step.args": "serve_batch_step",
    "step.dispatch": "serve_batch_step",
    "step.sync": "serve_batch_step",
    "step.scatter": "serve_batch_step",
    "step.ship": "step.scatter",
    "step.publish": "step.scatter",
    "tick.finish": "tick",
    # A family with a recurrent carry (CARRY_SPANS): its stack brought to
    # the seating, and, with the prefix cache on, a snapshot of the carry
    # stored with every published extent and an adopter's copy of one.
    # Those two are leaves wherever an extent is published or adopted, as
    # the memory plane's spans are wherever a page moves: each of their
    # parents is a name of this table.
    "step.carry": "serve_batch_step",
    "prefix.snapshot": ("prefill.ship", "step.ship", "step.args"),
    "prefix.restore": ("tick.match", "prefill.match", "prefill.ship",
                       "step.ship"),
}
CARRY_SPANS = ("step.carry", "prefix.snapshot", "prefix.restore")


def span_totals():
    from oncilla_tpu.utils.debug import GLOBAL_TRACER

    return {op: (v["count"], v["hist"]["sum_s"])
            for op, v in GLOBAL_TRACER.snapshot().items()}


def anatomy_prompts(cfg, seed):
    """A shared three-page prefix, then remainders that reach every span:
    sub-page tails (``step.publish``), a whole-page prompt (its first
    token comes from ``prefill.sync``) and enough new tokens to cross a
    page boundary while decoding (``step.ship``)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, cfg.vocab, 3 * P).tolist()
    return [base + rng.integers(1, cfg.vocab, n).tolist()
            for n in (5, 11, 2 * P, 3, P, 13)]


def run_unheld(model, prompts, *, new_tokens=6, **kw):
    """Serve ``prompts`` to the end without the dense reference: for a
    model of another family, whose own tests hold it to its own."""
    from oncilla_tpu.serving.engine import Request

    ctx, store, eng = build_engine(model, **kw)
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=new_tokens))
        assert len(eng.run()) == len(prompts)
    finally:
        eng.close()
        store.close()
        ctx.tini()


@pytest.mark.parametrize("family", ["dense", "carry"])
def test_tick_span_tree_covers_the_tick_with_one_parent_a_name(
        tiny_model, family):
    from oncilla_tpu.obs import journal

    if family == "carry":
        # The gated short-convolution family: a carry a session, small
        # enough that every published extent keeps a snapshot of it.
        import jax

        from oncilla_tpu.models import ConvMoeConfig

        cfg = ConvMoeConfig.tiny()
        tiny_model = (cfg, cfg.init_params(jax.random.key(0)))
        serve, table = run_unheld, TICK_SPANS
    else:
        serve = run_prompts
        table = {op: p for op, p in TICK_SPANS.items()
                 if op not in CARRY_SPANS}
    cfg, _ = tiny_model
    # A carry family's extents take two slots each: a page and a snapshot.
    kw = dict(share=True, hot=96 if family == "carry" else 48, warm=8,
              new_tokens=10, max_active=3, max_batch=2)
    serve(tiny_model, anatomy_prompts(cfg, 50), **kw)  # compiles
    was = journal.enabled()
    journal.set_enabled(True)
    try:
        # Coverage is a statement about wall time: a descheduled worker can
        # stretch one gap between two spans, so the best of three counts.
        for attempt in range(3):
            journal.clear()
            before = span_totals()
            serve(tiny_model, anatomy_prompts(cfg, 51 + attempt), **kw)
            after = span_totals()
            events = journal.events()
            count = {op: after[op][0] - before.get(op, (0, 0.0))[0]
                     for op in table if op in after}
            total = {op: after[op][1] - before.get(op, (0, 0.0))[1]
                     for op in table if op in after}
            shares = {
                parent: sum(total[c] for c, p in table.items()
                            if p == parent) / total[parent]
                for parent in ("tick", "serve_batch_step",
                               "serve_prefill_chunk", "step.scatter")}
            if min(shares.values()) >= 0.9:
                break
    finally:
        journal.set_enabled(was)
        journal.clear()
    assert set(count) == set(table)
    assert all(n > 0 for n in count.values()), count
    # children never exceed their parent, and leave under a tenth unnamed
    assert all(0.9 <= s <= 1.0 for s in shares.values()), shares
    # the journal's parentage: every span of a name hangs under the one
    # parent the table gives, the tick itself under nothing
    spans = [e for e in events if e["ev"] == "span"]
    op_of = {e["span_id"]: e["op"] for e in spans}
    seen = {}
    for e in spans:
        if e["op"] in table:
            parent = op_of.get(e["parent_span_id"])
            want = table[e["op"]]
            if isinstance(want, tuple):
                assert parent in want and parent in table, (e["op"],
                                                                 parent)
            else:
                assert parent == want, (e["op"], parent)
        seen.setdefault(e["op"], set()).add(op_of.get(e["parent_span_id"]))
    assert set(table) <= set(seen)
    # the memory plane's spans nest under them and stay leaves
    assert not set(table.values()) & {"alloc", "put", "get", "copy"}
    assert all(parents <= set(table) for op, parents in seen.items()
               if op in ("alloc", "put", "get", "copy"))
    # so the journal's critical-path attribution gets the tick's split from
    # the spans alone: no phase events are left to carve it
    from oncilla_tpu.obs import critpath

    assert not [e for e in events if e["ev"] == "phase"]
    trees = [t for t in critpath.assemble(events) if t["root_op"] == "tick"]
    assert len(trees) == count["tick"]
    named = set().union(*(t["attribution"] for t in trees))
    assert {"step.residency", "step.pool", "step.dispatch", "step.sync",
            "prefill.residency", "prefill.dispatch"} <= named
    assert min(t["attributed_frac"] for t in trees) > 0.99
    # one ttft event per request, found by its tenant
    ttft = [e for e in events if e["ev"] == "ttft"]
    assert sorted(e["tenant"] for e in ttft) == [f"t{i}" for i in range(6)]
    for e in ttft:
        assert e["queue_s"] + e["chunk_s"] + e["tail_s"] == pytest.approx(
            e["ttft_s"], abs=2e-6)


# -- 6. request anatomy: TTFT split where it is spent -----------------------


def run_with_ttft_log(tiny_model, prompts, **kw):
    """Run to completion; returns (results by tenant, the (seconds, parts)
    pairs ``note_ttft`` was handed, the stats snapshot)."""
    from oncilla_tpu.serving.engine import Request

    ctx, store, eng = build_engine(tiny_model, **kw)
    noted = []
    note = eng.stats.note_ttft

    def logging_note(seconds, **parts):
        noted.append((seconds, parts))
        note(seconds, **parts)

    eng.stats.note_ttft = logging_note
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=6))
        results = {r.tenant: r for r in eng.run()}
        snap = eng.stats.snapshot()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    return results, noted, snap


def test_ttft_parts_sum_to_the_recorded_ttft(tiny_model):
    cfg, _ = tiny_model
    # Six requests on three seats of max_active: the last three queue.
    results, noted, snap = run_with_ttft_log(
        tiny_model, anatomy_prompts(cfg, 60), share=True, hot=48, warm=8,
        max_active=3, max_batch=3)
    assert len(noted) == len(results) == 6
    for seconds, parts in noted:
        assert set(parts) == {"queue_s", "chunk_s", "tail_s",
                              "unseated_ticks"}
        assert parts["queue_s"] + parts["chunk_s"] + parts["tail_s"] == (
            pytest.approx(seconds, abs=1e-6))
        assert min(parts["queue_s"], parts["chunk_s"], parts["tail_s"]) >= 0
    assert [r.ttft_parts for r in results.values()] == [p for _, p in noted]
    queue = [results[f"t{i}"].ttft_parts["queue_s"] for i in range(6)]
    # admitted at once: a tick's start away from submit; the rest waited
    # for a finished session's place, which takes whole ticks
    assert 0 < max(queue[:3]) < min(queue[3:])
    # t2 is whole pages (its last chunk emits the token): no tail at all;
    # t0's 5-token remainder rides the fused step
    assert results["t2"].ttft_parts["tail_s"] == 0
    assert results["t2"].ttft_parts["chunk_s"] > 0
    assert results["t0"].ttft_parts["tail_s"] > 0
    ttft = snap["ttft"]
    assert ttft["count"] == 6
    parts = ttft["parts"]
    assert parts["queue_s"] + parts["chunk_s"] + parts["tail_s"] == (
        pytest.approx(ttft["sum_s"], abs=1e-5))
    assert parts["unseated_ticks"] == sum(
        p["unseated_ticks"] for _, p in noted)


def test_unseated_ticks_count_runnable_sessions_without_a_seat(tiny_model):
    cfg, _ = tiny_model
    rng = np.random.default_rng(61)
    # Four sub-page prompts, all runnable from the first tick, two seats.
    prompts = [rng.integers(1, cfg.vocab, 6).tolist() for _ in range(4)]
    results, noted, snap = run_with_ttft_log(
        tiny_model, prompts, share=False, max_active=4, max_batch=2)
    ticks = [results[f"t{i}"].ttft_parts["unseated_ticks"] for i in range(4)]
    # the first two keep their seats; the others wait for them, a tick
    # each time: 6 prompt tokens + 5 more outputs of the seated pair
    assert ticks[:2] == [0, 0] and min(ticks[2:]) > 0
    assert snap["ttft"]["parts"]["unseated_ticks"] == sum(ticks)
    assert snap["preempts"]["slot"] >= sum(ticks)
    # nothing queued and no whole page: all of it is the tail
    for _, parts in noted:
        assert parts["chunk_s"] < 1e-3 < parts["tail_s"]


# -- 7. the page pool kept on the device between ticks ----------------------


def watch_pool(eng):
    """Wrap ``eng._batch_pool``: after every call, check each pool row the
    table names against the entries it stands for, and log (keys the pool
    held before, capacity before, this batch's distinct keys, capacity
    after). A row is bitwise the ``arrays`` of the entry first seated under
    its key. Entries that share a key hold the same page and the same bits:
    a shared page has one entry, and a CoW adopter whose finished page is
    folded onto the leader's at publish takes the leader's. The keys of
    entries that agree with their row to rounding only are returned beside
    the log: there should be none."""
    calls = []
    inexact = set()
    seated: dict = {}
    inner = eng._batch_pool

    def watched(batch):
        held = set(eng._pool_slots[0])
        cap0 = None if eng._pool_k is None else eng._pool_k.shape[0]
        # the dense family's page has one kind: one pool, one table
        pools, tabs, by_kind = inner(batch)
        ((pool_k, pool_v),), (table,), (tables,) = pools, tabs, by_kind
        pk, pv = np.asarray(pool_k), np.asarray(pool_v)
        keys = []
        for b, sess in enumerate(batch):
            live = [e for e in sess.entries if not e.pending_fill]
            assert len(live) == len(tables[b])
            for i, e in enumerate(live):
                key = (e.page.page_id, e.version)
                mine = [np.asarray(a)[:, 0] for a in e.arrays]
                if key not in keys:
                    keys.append(key)
                    if key not in held:
                        seated[key] = mine
                row = table[b, i]
                assert eng._pool_slots[0][key] == row
                assert np.array_equal(pk[row], seated[key][0])
                assert np.array_equal(pv[row], seated[key][1])
                if not (np.array_equal(pk[row], mine[0])
                        and np.array_equal(pv[row], mine[1])):
                    inexact.add(key)
                    assert e.extent is not None
                    np.testing.assert_allclose(pk[row], mine[0], atol=1e-5)
                    np.testing.assert_allclose(pv[row], mine[1], atol=1e-5)
        slots = eng._pool_slots[0]
        assert len(set(slots.values())) == len(slots)
        assert not set(slots.values()) & set(eng._pool_free[0])
        calls.append((held, cap0, keys, pk.shape[0]))
        return pools, tabs, by_kind

    eng._batch_pool = watched
    return calls, inexact


def expected_pool_counters(calls):
    """The counters the logged calls must have produced: a key is written
    when the pool did not hold it, reused when it did (a change of the row
    bucket carries the batch's rows over: nothing is written for them); a
    pool is built when the row bucket changes."""
    want = {"rows_reused": 0, "rows_written": 0, "rebuilds": 0}
    for held, cap0, keys, cap1 in calls:
        want["rebuilds"] += cap0 != cap1
        new = [k for k in keys if k not in held]
        want["rows_written"] += len(new)
        want["rows_reused"] += len(keys) - len(new)
    return want


def test_pool_rows_are_the_entries_arrays_after_every_tick(tiny_model):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    # The churned, prefix-sharing, CoW workload of the paired gate above.
    prompts = seeded_prompts(cfg, 11, n=5, shared=20, suffix=4)
    ctx, store, eng = build_engine(tiny_model, share=True, hot=2, warm=2,
                                  max_active=4)
    calls, inexact = watch_pool(eng)
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=8))
        eng.run()
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    assert len(calls) == meta["batch"]["steps"] > 0
    assert meta["moves"]["promote"] > 0 and meta["prefix"]["cow"] >= 1
    # rows written = keys new to the pool; a pool is built only when the
    # bucket of distinct rows changes
    assert meta["pool"] == expected_pool_counters(calls)
    assert meta["pool"]["rows_reused"] > meta["pool"]["rows_written"] > 0
    buckets = [cap for _, _, _, cap in calls]
    assert meta["pool"]["rebuilds"] == 1 + sum(
        a != b for a, b in zip(buckets, buckets[1:]))
    # every entry agreed with its row bit for bit, the folded CoW page
    # (t1's last prompt page) too: it took the leader's entry
    assert not inexact


def test_steady_decode_writes_one_row_a_shipped_page(tiny_model):
    cfg, _ = tiny_model
    rng = np.random.default_rng(71)
    # Five whole pages and three tokens of prompt, then three pages' length
    # of decode: 5, 6, 7, 8 distinct rows, one bucket (8) all the way.
    prompt = rng.integers(1, cfg.vocab, 5 * P + 3).tolist()
    outs, meta, _ = run_prompts(tiny_model, [prompt], new_tokens=3 * P,
                                share=False, hot=16, warm=4)
    assert len(outs["t0"]) == 3 * P
    steps = meta["batch"]["steps"]
    assert steps == 3 + 3 * P - 1
    pool = meta["pool"]
    # one pool, built at the first step with the five prompt pages; then
    # one row for each of the three pages the decode shipped
    assert pool["rebuilds"] == 1
    assert pool["rows_written"] == 5 + 3
    # rows referenced over the run, less the eight written
    assert pool["rows_reused"] == (5 * P + 6 * P + 7 * P + 8 * 2) - 8


def test_promoted_page_gets_its_row_rewritten(tiny_model):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(73)
    prompt = rng.integers(1, cfg.vocab, 2 * P + 2).tolist()
    ctx, store, eng = build_engine(tiny_model, share=False, hot=4, warm=4)
    calls, _ = watch_pool(eng)
    try:
        eng.submit(Request(tenant="t0", tokens=list(prompt),
                           max_new_tokens=5))
        while not calls:
            eng._tick()
        eng._tick()
        sess = eng.active[0]
        entry = sess.entries[0]
        old_key = (entry.page.page_id, entry.version)
        before = eng.stats.snapshot()["pool"]
        assert old_key in eng._pool_slots[0]
        # Demoted under the seated session: the next step faults it back
        # with a new version, i.e. a new key for the same page.
        store.demote(entry.page, Tier.WARM)
        eng._tick()
        new_key = (entry.page.page_id, entry.version)
        after = eng.stats.snapshot()["pool"]
        assert new_key != old_key and new_key[0] == old_key[0]
        assert new_key in eng._pool_slots[0]
        # two rows, two slots: the stale key gave its row up
        assert old_key not in eng._pool_slots[0]
        assert after["rows_written"] == before["rows_written"] + 1
        assert after["rows_reused"] == before["rows_reused"] + 1
        assert after["rebuilds"] == before["rebuilds"] == 1
        results = eng.run()
        assert eng.stats.snapshot()["moves"]["promote"] >= 1
    finally:
        eng.close()
        store.close()
        ctx.tini()
    # The rewritten row holds the page: the logits are the reference's.
    outs = held_to_reference(tiny_model, [prompt], results)
    assert len(outs["t0"]) == 5


# -- 7a. a shared page's decode arrays are kept once, by the page -----------


class ArraysWatch:
    """Log what an engine does about its pages' decode arrays: the pages a
    session takes into its context (``take``: adopted in ``_match_more``,
    shipped, or folded onto a winner's page at a dedup), every rebuild from
    the store's bytes, and the pages a finishing session held. Counts the
    ``_unpack`` calls and the ``read_page`` calls that found their page HOT,
    and keeps the bits of every page as its publisher shipped it. After every
    tick, every entry of a shared page is the one entry of that page and
    holds the publisher's bits."""

    def __init__(self):
        self.log: list[tuple] = []
        self.unpacks = 0
        self.hot_reads = 0
        self.bits: dict[int, list] = {}
        self.refs: list = []
        self._stored = None

    def __call__(self, eng):
        unpack, read = eng._unpack, eng.store.read_page
        alloc, write = eng.store.alloc_page, eng.store.write_page
        match, ship = eng._match_more, eng._ship
        rebuild, finish, tick = eng._rebuild, eng._finish, eng._tick

        def counted_unpack(data, kind=0):
            self.unpacks += 1
            return unpack(data, kind)

        def counted_read(page, out=None):
            self.hot_reads += page.tier == Tier.HOT
            return read(page, out)

        def logged_match(sess):
            n = len(sess.entries)
            match(sess)
            for e in sess.entries[n:]:
                if not e.pending_fill:
                    self.log.append(("take", sess.req.tenant,
                                     e.page.page_id))

        def noting_alloc(data, **kw):
            page = alloc(data, **kw)
            self._stored = page.page_id
            return page

        def noting_write(page, data):
            self._stored = page.page_id
            write(page, data)

        def logged_ship(sess):
            ship(sess)
            e = sess.entries[-1]
            # The page the tail was stored in is the session's page, unless
            # a dedup at publish folded it onto the winner's.
            own = e.page.page_id == self._stored
            self.log.append(("ship" if own else "take", sess.req.tenant,
                             e.page.page_id))
            if own:
                # copies: on the CPU a view would keep the arrays alive
                self.bits[e.page.page_id] = [np.array(a) for a in e.arrays]
                self.refs.append(weakref.ref(e.arrays[0]))

        def logged_rebuild(e, data):
            self.log.append(("rebuild", None, e.page.page_id))
            rebuild(e, data)

        def logged_finish(sess, abandon=False):
            self.log.append(("finish", sess.req.tenant,
                             [e.page.page_id for e in sess.entries]))
            finish(sess, abandon)

        def checked_tick():
            tick()
            by_page = {}
            for sess in eng.active:
                for e in sess.entries:
                    if e.extent is None:
                        continue
                    pid = e.page.page_id
                    assert by_page.setdefault(pid, e) is e
                    assert eng._shared[pid] is e
                    if eng._resident(e) and pid in self.bits:
                        for mine, want in zip(e.arrays, self.bits[pid]):
                            assert np.array_equal(np.asarray(mine), want)
            assert set(eng._shared) == set(by_page)

        eng._unpack, eng.store.read_page = counted_unpack, counted_read
        eng.store.alloc_page, eng.store.write_page = noting_alloc, noting_write
        eng._match_more, eng._ship = logged_match, logged_ship
        eng._rebuild, eng._finish, eng._tick = (
            logged_rebuild, logged_finish, checked_tick)

    def replay(self) -> dict:
        """The counters the log must have produced, by the rule alone: a
        session that takes a page some live session already holds shares
        that holder's arrays; a page is held until the last session that
        took or shipped it finishes."""
        want = {"pages_shared": 0, "pages_rebuilt": 0}
        holders: dict = {}
        for what, tenant, pid in self.log:
            if what == "finish":
                for p in pid:
                    holders.get(p, set()).discard(tenant)
            elif what == "rebuild":
                want["pages_rebuilt"] += 1
            else:
                want["pages_shared"] += (what == "take"
                                         and bool(holders.get(pid)))
                holders.setdefault(pid, set()).add(tenant)
        return want

    def alive(self) -> int:
        """Shipped pages' arrays that something still holds."""
        gc.collect()
        return sum(r() is not None for r in self.refs)


def prefix_prompts(cfg, seed, *, n, pages, suffixes=None):
    """``n`` prompts on one prefix of ``pages`` whole pages, each with a
    sub-page remainder of its own (no two alike: no CoW adoption)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(1, cfg.vocab, pages * P).tolist()
    suffixes = suffixes or [2 + i % (P - 2) for i in range(n)]
    return [base + rng.integers(1, cfg.vocab, k).tolist() for k in suffixes]


def test_adopters_take_the_publishers_arrays_as_they_are(tiny_model):
    cfg, _ = tiny_model
    prompts = prefix_prompts(cfg, 91, n=4, pages=3)
    watch = ArraysWatch()
    pool_log = []

    def both(eng):
        pool_log.append(watch_pool(eng))
        watch(eng)

    outs, meta, _ = run_prompts(tiny_model, prompts, watch=both,
                                share=True, hot=32, warm=4, max_active=4)
    # Every prefix page was computed and shipped once, by whoever got there
    # first; the other three sessions took it: twelve page-places, three
    # ships. Nobody pulled a HOT page's bytes back or took a page apart.
    takes = [e for e in watch.log if e[0] == "take"]
    assert len(takes) == 9 and len({pid for _, _, pid in takes}) == 3
    assert watch.unpacks == 0 and watch.hot_reads == 0
    assert meta["arrays"] == watch.replay() == {
        "pages_shared": 9, "pages_rebuilt": 0}
    assert meta["prefix"]["hits"] >= 9
    # the pool's rows are the entries' arrays, which are the publishers'
    # bits (ArraysWatch, after every tick): no second pattern under a key
    (calls, inexact), = pool_log
    assert calls and not inexact


def test_a_pages_arrays_live_as_long_as_a_session_holds_the_page(tiny_model):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    prompts = prefix_prompts(cfg, 93, n=6, pages=3)
    ctx, store, eng = build_engine(tiny_model, share=True, hot=48, warm=4,
                                   max_active=3)
    watch = ArraysWatch()
    watch(eng)
    try:
        results = []
        for wave in (range(0, 3), range(3, 6)):
            for i in wave:
                eng.submit(Request(tenant=f"t{i}", tokens=list(prompts[i]),
                                   max_new_tokens=5))
            before = eng.stats.snapshot()["arrays"]
            unpacks = watch.unpacks
            n_log = len(watch.log)
            results += eng.run()
            # the last session that held a page has finished: nothing is
            # kept for it, though the extents stay in the trie
            assert not eng._shared and watch.alive() == 0
            assert len(eng.prefix.extents()) >= 3
        after = eng.stats.snapshot()["arrays"]
        # The second wave found the prefix in the trie and its arrays gone:
        # each page was rebuilt once, by the first session to need it, and
        # the two others took it as it was.
        rebuilt = [pid for what, _, pid in watch.log[n_log:]
                   if what == "rebuild"]
        assert len(rebuilt) == len(set(rebuilt)) == 3
        assert watch.unpacks - unpacks == 3
        assert after["pages_rebuilt"] - before["pages_rebuilt"] == 3
        assert after["pages_shared"] - before["pages_shared"] == 6
        assert after == watch.replay()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    held_to_reference(tiny_model, prompts, results)


@pytest.mark.parametrize("case", ["demoted", "rewritten"])
def test_a_page_lost_under_two_sharers_is_rebuilt_once(tiny_model, case):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    prompts = prefix_prompts(cfg, 95, n=2, pages=2, suffixes=[3, 5])
    ctx, store, eng = build_engine(tiny_model, share=True, hot=16, warm=4,
                                   max_active=2)
    watch = ArraysWatch()
    calls, inexact = watch_pool(eng)
    watch(eng)
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=2 * P))
        while len(calls) < 2 or len(calls[-1][2]) < 2:
            eng._tick()
        a, b = eng.active
        entry = a.entries[0]
        assert entry is b.entries[0] and eng._resident(entry)
        page, old_key = entry.page, (entry.page.page_id, entry.version)
        before = eng.stats.snapshot()
        unpacks = watch.unpacks
        if case == "demoted":
            store.demote(page, Tier.WARM)
        else:
            # The store refuses a write under live references; what a
            # rewrite does to the holders is a new version of the page.
            raw = np.array(store.read_page(page), copy=True)
            refs, page.refs = page.refs, 0
            store.write_page(page, raw)
            page.refs = refs
        assert not eng._resident(entry)
        eng._tick()
        after = eng.stats.snapshot()
        new_key = (page.page_id, entry.version)
        assert eng._resident(entry) and new_key != old_key
        assert a.entries[0] is entry and b.entries[0] is entry
        # one rebuild and one row for the two of them
        assert watch.unpacks == unpacks + 1
        assert (after["arrays"]["pages_rebuilt"]
                == before["arrays"]["pages_rebuilt"] + 1)
        assert after["pool"]["rows_written"] == (
            before["pool"]["rows_written"] + 1)
        assert new_key in eng._pool_slots[0]
        assert old_key not in eng._pool_slots[0]
        assert after["moves"]["promote"] - before["moves"]["promote"] == (
            case == "demoted")
        # (the engine's weak table must not see this test's references)
        del a, b, entry
        results = eng.run()
        assert eng.stats.snapshot()["arrays"] == watch.replay()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    assert not inexact
    outs = held_to_reference(tiny_model, prompts, results)
    assert [len(outs[f"t{i}"]) for i in range(2)] == [2 * P, 2 * P]


def test_without_a_prefix_cache_no_page_is_shared(tiny_model):
    cfg, _ = tiny_model
    # The churned workload of the paired gate above, the prefix cache off:
    # no page is ever in two contexts. Commit 57b5cae (before a page kept
    # its arrays) made 105 _unpack calls and wrote 93 rows on it.
    prompts = seeded_prompts(cfg, 11, n=5, shared=20, suffix=4)
    watch = ArraysWatch()
    outs, meta, _ = run_prompts(tiny_model, prompts, watch=watch,
                                share=False, hot=2, warm=2, new_tokens=8,
                                max_active=4)
    assert meta["arrays"] == watch.replay() == {
        "pages_shared": 0, "pages_rebuilt": 105}
    assert watch.unpacks == 105
    assert meta["pool"]["rows_written"] == 93
    assert meta["moves"]["promote"] > 0 and meta["moves"]["demote"] > 0


def test_pool_write_program_compiles_once_a_capacity(tiny_model, monkeypatch):
    import oncilla_tpu.serving.engine as engine_mod
    from oncilla_tpu.models import paged_decode_batch_step_jit as step

    write, gather = engine_mod._pool_write_jit, engine_mod._pool_gather_jit
    cfg, _ = tiny_model
    rng = np.random.default_rng(79)
    prompts = [rng.integers(1, cfg.vocab, ln).tolist()
               for ln in (5, 9, 17, 25, 30)]
    # The programs' own cache is process-wide (a whole run's other tests
    # have filled it), so count what THIS workload hands the group write
    # and the gather: one program a distinct pool shape, or pair of them.
    shapes, crossings = [], []

    def recording(pool, pages, slots):
        assert len(pages) == len(slots) <= engine_mod._POOL_GROUP
        shapes.append(pool[0].shape)
        return write(pool, pages, slots)

    def crossing(pool, idx):
        crossings.append((pool[0].shape[0], len(idx)))
        return gather(pool, idx)

    monkeypatch.setattr(engine_mod, "_pool_write_jit", recording)
    monkeypatch.setattr(engine_mod, "_pool_gather_jit", crossing)

    def workload():
        return run_prompts(tiny_model, prompts, new_tokens=12,
                           share=False, hot=8, warm=8, max_active=5)

    outs, meta, _ = workload()
    built = (step._cache_size(), write._cache_size(), gather._cache_size())
    # the write programs of a pool capacity: those the run reached (1..16
    # rows) and their neighbours, never one a row or a tick; one gather a
    # pair of capacities, mostly neighbours
    assert meta["pool"]["rebuilds"] >= 2
    assert 0 < len(set(shapes)) <= 6 < meta["pool"]["rows_written"]
    assert {s[0] for s in shapes} <= {1, 2, 4, 8, 16, 32}
    assert {max(a, b) // min(a, b) for a, b in crossings} <= {2, 4}
    assert meta["pool_dispatches"]["gathers"] == meta["pool"]["rebuilds"] - 1
    # a second identical workload builds nothing more, for the fused step,
    # the group write and the gather
    outs2, meta2, _ = workload()
    assert (step._cache_size(), write._cache_size(),
            gather._cache_size()) == built
    assert outs2 == outs and meta2["pool"] == meta["pool"]
    assert meta2["pool_dispatches"] == meta["pool_dispatches"]


# -- 8. the seated sessions' tails kept in one stack between ticks ----------


def test_steady_decode_keeps_every_seat(tiny_model, monkeypatch):
    import oncilla_tpu.serving.engine as engine_mod
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(83)
    # Four sessions in step with each other: five prompt tokens through the
    # fused step, then three pages' length of decode. Each crosses three
    # page boundaries, all finish in one tick.
    prompts = [rng.integers(1, cfg.vocab, 5).tolist() for _ in range(4)]
    new = 3 * P
    watch = SeatWatch(monkeypatch)
    # What the engine itself asks of jax.numpy by name, after the first step,
    # and the ships' one packing program.
    asked = dict.fromkeys(("concatenate", "stack"), 0)
    packs = []
    pack = engine_mod._pack_pages_jit
    monkeypatch.setattr(
        engine_mod, "_pack_pages_jit",
        lambda kinds, dtype: packs.append(len(kinds)) or pack(kinds, dtype))

    class CountingJnp:
        def __getattr__(self, name):
            real = getattr(engine_mod.jax.numpy, name)
            if name not in asked:
                return real

            def call(*args, **kw):
                asked[name] += 1
                return real(*args, **kw)
            return call

    ctx, store, eng = build_engine(tiny_model, share=False, hot=32, warm=4,
                                  max_active=4)
    try:
        watch(eng)
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=new))
        eng._tick()
        first = eng.stats.snapshot()
        assert first["batch"]["steps"] == 1
        # The first seating: four seats placed in a new stack; nothing was
        # written, since every tail was empty.
        assert first["tails"] == {"seats_kept": 0, "seats_written": 4}
        assert watch.widths == [4] and not any(watch.calls.values())
        monkeypatch.setattr(engine_mod, "jnp", CountingJnp())
        results = eng.run()
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    steps = 5 + new - 1
    assert meta["batch"]["steps"] == steps
    assert meta["batch"]["size_sum"] == 4 * steps
    assert meta["tails"] == {"seats_kept": 4 * (steps - 1),
                             "seats_written": 4}
    # A page boundary is one read of the seat and nothing else: no tail is
    # cut out for the next step, none glued back, and no session is given a
    # tail of its own (SeatWatch: every seated session holds None).
    assert watch.widths == [4]
    assert watch.calls == {"_seat_write_jit": 0, "_seat_move_jit": 0,
                           "_seat_read_jit": 4 * 3}
    # ... and one dispatch that packs the page for the store (its stack is
    # traced once, if no earlier engine had it traced).
    assert packs == [1] * (4 * 3)
    assert asked["concatenate"] == 0 and asked["stack"] <= 1
    monkeypatch.undo()
    outs = held_to_reference(tiny_model, prompts, results)
    assert [len(outs[f"t{i}"]) for i in range(4)] == [new] * 4


def published_partial(eng, prompt):
    """The bytes of the partial page published under a sub-page prompt,
    as float32 (leaf, L, 1, KV, P, Hd)."""
    ext = eng.prefix.child(None, tuple(prompt))
    assert ext is not None and ext.fill == len(prompt) < P
    raw = np.array(eng.store.read_page(ext.page), copy=True)
    return raw.view(np.float32).reshape(eng.page_shapes[0])


def test_partial_from_a_used_seat_is_zeros_beyond_its_fill(tiny_model):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(89)
    first = rng.integers(1, cfg.vocab, 3).tolist()
    second = rng.integers(1, cfg.vocab, 4).tolist()

    def serve(prompts):
        # One session at a time, so the second sits down where the first
        # sat: a seat that held a whole shipped page and three tokens more.
        ctx, store, eng = build_engine(tiny_model, share=True, hot=16,
                                      warm=4, max_active=1)
        try:
            for i, p in enumerate(prompts):
                eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                                   max_new_tokens=P + 1))
            outs = {r.tenant: list(r.out_tokens) for r in eng.run()}
            meta = eng.metrics_meta()
            return outs, meta, published_partial(eng, second)
        finally:
            eng.close()
            store.close()
            ctx.tini()

    outs_used, meta, used = serve([first, second])
    outs_fresh, _, fresh = serve([second])
    # one stack of one seat all the way; the second session wrote nothing
    assert meta["tails"]["seats_written"] == 1
    assert outs_used["t1"] == outs_fresh["t0"]
    assert used[..., :len(second), :].any()
    assert not used[..., len(second):, :].any()
    assert used.tobytes() == fresh.tobytes()


@pytest.mark.parametrize("case", [
    "adopts-a-partial-mid-batch", "loses-its-seat-to-a-higher-class",
    "unseated-with-an-empty-tail", "unseated-with-tokens-in-its-tail"])
def test_a_seat_changing_hands_serves_the_reference_tokens(
        tiny_model, monkeypatch, case):
    from oncilla_tpu.qos.policy import PRIO_HIGH
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(97)
    prompts = [rng.integers(1, cfg.vocab, n).tolist()
               for n in (P + 3, 5, 6, 4)]
    budgets = [2 * P, 2 * P, 2 * P, P]
    adopts = case == "adopts-a-partial-mid-batch"
    if adopts:
        prompts[3] = list(prompts[0])   # t3 comes later with t0's prompt
    late = Request(tenant="t3", tokens=list(prompts[3]),
                   max_new_tokens=budgets[3])
    if case == "loses-its-seat-to-a-higher-class":
        late.priority = PRIO_HIGH
    watch = SeatWatch(monkeypatch)
    # Three or four sessions: one stack of four seats all the way.
    ctx, store, eng = build_engine(tiny_model, share=True, hot=16, warm=4,
                                  max_active=4, max_batch=4 if adopts else 3)
    try:
        watch(eng)
        for i, p in enumerate(prompts[:3]):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=budgets[i]))
        # t0 prefills a page, then all three ride the step for five tokens:
        # t0's partial is published, every tail holds tokens.
        for _ in range(5):
            eng._tick()
        t0, t1, t2 = eng.active
        assert [s.seat for s in eng.active] == [0, 1, 2]
        assert [s.tail_len for s in eng.active] == [5, 5, 5]
        calls0 = dict(watch.calls)

        def since(name):
            return watch.calls[name] - calls0[name]

        if case.startswith("unseated"):
            # What _prefill_chunk does at its head to a session that comes
            # back from the step (no schedule sends one back today).
            want_empty = case == "unseated-with-an-empty-tail"
            while (t0.tail_len == 0) != want_empty:
                eng._tick()
            eng._unseat(t0)
            assert t0.seat is None and eng._seats[0] is None
            assert all(t.shape == eng._leaf_shapes[0] for t in t0.tails)
            assert any(np.asarray(t).any() for t in t0.tails) != want_empty
            calls0 = dict(watch.calls)
            eng._tick()
            # t0 sat down where it had sat; its tail was written only if
            # it held anything, and nobody was moved for it
            assert t0.seat == 0 and t0.tails is None
            assert since("_seat_write_jit") == (not want_empty)
            assert since("_seat_move_jit") == 0
        eng.submit(late)
        results = eng.run()
        outs = {r.tenant: list(r.out_tokens) for r in results}
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    assert set(watch.widths) <= {4, 2, 1} and watch.widths[0] == 4
    if adopts:
        # t3 took t0's page and its partial, and sat down beside the three
        # with 2 adopted tokens in its tail: one write, in the one stack.
        assert meta["prefix"]["cow"] == 1
        assert ["t0", "t1", "t2", "t3"] in watch.steps
        assert watch.widths.count(4) == 1
        assert outs["t3"] == outs["t0"][:P]
    elif case == "loses-its-seat-to-a-higher-class":
        # t3 took t2's seat while t2 lived on: t2's tail was read out, and
        # written back when it sat down again.
        assert meta["preempts"].get("slot", 0) > 0
        assert ["t0", "t1", "t3"] in watch.steps
    if not case.startswith("unseated"):
        assert since("_seat_write_jit") >= 1
    monkeypatch.undo()
    held_to_reference(tiny_model, prompts, results)
    assert [len(outs[f"t{i}"]) for i in range(4)] == budgets


def test_seat_programs_are_built_with_the_stack_not_at_a_seat_change(
        tiny_model, monkeypatch):
    import oncilla_tpu.serving.engine as engine_mod

    cfg, _ = tiny_model
    rng = np.random.default_rng(79)
    prompts = [rng.integers(1, cfg.vocab, ln).tolist()
               for ln in (5, 9, 17, 25, 30)]
    # The seat programs are the module's: what an earlier test of this
    # process built is built. Counted from their own cleared caches, the
    # builds are this engine's, whatever ran before.
    for name in SeatWatch.PROGRAMS:
        getattr(engine_mod, name).clear_cache()
    watch = SeatWatch(monkeypatch)
    assert watch.cache_sizes() == (0, 0, 0)
    # Batches of 5 down to 1 as the sessions finish, each from whatever seat
    # it has: joins, moves and reads at every width.
    outs, meta, _ = run_prompts(
        tiny_model, prompts, new_tokens=[12, 4, 9, 6, 12], share=False,
        hot=8, warm=8, max_active=5, max_batch=8, watch=watch)
    assert sorted(set(watch.widths)) == [1, 2, 4, 8]
    assert all(watch.calls.values())
    # Whatever was built was built while a stack was made, never between
    # two of them ...
    ends = [after for _, after in watch.built] + [watch.cache_sizes()]
    assert all(before == ends[i]
               for i, (before, _) in enumerate(watch.built[1:]))
    assert ends[-1] == ends[-2]
    # ... a stack builds the programs of its own width and of the next one
    # up (the widest is max_batch's), one of each a width, and a width that
    # comes round again builds nothing.
    ready = set()
    for b_pad, (before, after) in zip(watch.widths, watch.built):
        new = {b_pad, min(2 * b_pad, 8)} - ready
        assert after == tuple(n + len(new) for n in before), (b_pad, ready)
        ready |= new
    assert ready == {1, 2, 4, 8} and ends[-1] == (4, 4, 4)
    assert (meta["tails"]["seats_kept"] + meta["tails"]["seats_written"]
            == meta["batch"]["size_sum"])


def test_a_step_that_raises_leaves_nobody_seated(tiny_model, monkeypatch):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(101)
    prompts = [rng.integers(1, cfg.vocab, 5).tolist() for _ in range(3)]
    ctx, store, eng = build_engine(tiny_model, share=False, hot=16, warm=4,
                                  max_active=3)
    try:
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=4))
        eng._tick()
        assert [s.seat for s in eng.active] == [0, 1, 2]
        step = eng.family.step

        def failing(*args):
            raise RuntimeError("device lost")

        # The stack is donated to the step: one that raises hands nothing
        # back, so no session may point into it afterwards.
        eng.family = dataclasses.replace(eng.family, step=failing)
        with pytest.raises(RuntimeError, match="device lost"):
            eng._tick()
        assert eng._tails is None and eng._seats == []
        assert all(s.seat is None for s in eng.active)
        eng.family = dataclasses.replace(eng.family, step=step)
    finally:
        # abandons the three without reading a seat that is not there
        eng.close()
        store.close()
        ctx.tini()
    assert eng.active == []


def test_a_session_that_is_over_stands_up_and_takes_no_tail(
        tiny_model, monkeypatch):
    from oncilla_tpu.serving.engine import Request

    cfg, _ = tiny_model
    rng = np.random.default_rng(103)
    prompts = [rng.integers(1, cfg.vocab, 5).tolist() for _ in range(3)]
    watch = SeatWatch(monkeypatch)
    ctx, store, eng = build_engine(tiny_model, share=False, hot=16, warm=4,
                                  max_active=3)
    try:
        watch(eng)
        unseated, unseat = [], eng._unseat
        eng._unseat = lambda s: (unseated.append(s.req.tenant), unseat(s))[1]
        for i, (p, n) in enumerate(zip(prompts, (6, 2, 6))):
            eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                               max_new_tokens=n))
        t0, t1, t2 = (eng._tick(), *eng.active)[1:]
        while not t1.done:
            eng._tick()
        # t1 finished from the middle seat: vacated in the tick it finished
        # in, before any later seating looks at it.
        assert t1.seat is None and eng._seats == [t0, None, t2]
        # t0 is abandoned with tokens in its tail: it stands up as well,
        # and nobody reads the tail of a session that is over.
        assert t0.tail_len and not t0.done
        eng._finish(t0, abandon=True)
        eng.active.remove(t0)
        assert t0.seat is None and eng._seats == [None, None, t2]
        results = eng.run()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    # t2 went on alone, from seat 2 of the stack of four to a stack of one:
    # the one tail that was read out for a session to take along
    assert watch.steps[-1] == ["t2"] and watch.widths == [4, 1]
    assert unseated == ["t2"]
    monkeypatch.undo()
    outs = held_to_reference(tiny_model, prompts, results)
    assert {t: len(o) for t, o in outs.items()} == {"t1": 2, "t2": 6}
