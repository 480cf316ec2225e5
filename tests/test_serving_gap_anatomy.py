"""The tails' anatomy (docs/OBSERVABILITY.md, "Serving tick anatomy"): the
tick's phase account, the gaps and first-token waits it puts down to the
phases they spanned, and the two histograms with parts in ``ServingStats``.
CPU, tiny dense model: counts, sums and shares; times come from the chip.
"""

from __future__ import annotations

import contextlib
import json
import time

import numpy as np
import pytest

import oncilla_tpu as ocm
from oncilla_tpu.qos.policy import PRIO_HIGH
from oncilla_tpu.serving.engine import Request, ServingEngine
from oncilla_tpu.serving.metrics import GAP_BUCKETS, GAP_PHASES, ServingStats
from oncilla_tpu.serving.tiers import TieredPageStore

P = 8  # page_tokens
PHASE_FIELDS = tuple(f"{p}_s" for p in GAP_PHASES)


@pytest.fixture(scope="module")
def tiny_model():
    from oncilla_tpu.models import LlamaConfig, init_params_host

    cfg = LlamaConfig.tiny()
    return cfg, init_params_host(0, cfg)


@contextlib.contextmanager
def engine(tiny_model, name="t", **kw):
    cfg, params = tiny_model
    ctx = ocm.Ocm(config=ocm.OcmConfig(
        host_arena_bytes=1 << 20, device_arena_bytes=1 << 20))
    store = TieredPageStore(
        ctx, ServingEngine.page_nbytes(cfg, P), hot_capacity=64,
        warm_capacity=4, stats=ServingStats(name))
    eng = ServingEngine(params, cfg, store, None, page_tokens=P,
                        prefetch_workers=0, name=name, **kw)
    try:
        yield eng
    finally:
        eng.close()
        store.close()
        ctx.tini()


def prompts_of(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, n).tolist() for n in lengths]


def submit(eng, prompts, new_tokens=6, first=0, **kw):
    for i, p in enumerate(prompts, first):
        eng.submit(Request(tenant=f"t{i}", tokens=list(p),
                           max_new_tokens=new_tokens, **kw))


class Clients:
    """Every client at once, as ``benchmark/harness.py::Loop``: after each
    tick, stamp each session's new tokens, once with the caller's clock and
    once with the moment the engine closed the tick's books."""

    def __init__(self, eng):
        self.eng = eng
        self.stamps: dict[str, list] = {}
        self.marks: dict[str, list] = {}
        self.results = {}

    def tick(self):
        eng = self.eng
        eng._tick()
        now = time.perf_counter()
        done, eng.results = eng.results, []
        outs = [(s.req.tenant, len(s.out)) for s in eng.active]
        outs += [(r.tenant, len(r.out_tokens)) for r in done]
        for tenant, n in outs:
            new = n - len(self.stamps.setdefault(tenant, []))
            self.stamps[tenant] += [now] * new
            self.marks.setdefault(tenant, []).extend([eng._mark_t] * new)
        self.results.update((r.tenant, r) for r in done)

    def run(self):
        while self.eng.queue or self.eng.active:
            self.tick()
        return self

    @property
    def tokens(self) -> int:
        return sum(len(s) for s in self.stamps.values())

    @property
    def emitters(self) -> int:
        return sum(1 for s in self.stamps.values() if s)


def gaps_log(eng):
    """Every ``note_gaps`` call, from here on, a gap a row: the parts, the
    ticks and the first tokens it was handed."""
    calls, note = [], eng.stats.note_gaps

    def logging_note(gaps, firsts):
        parts = [p for p, _, count in gaps for _ in range(count)]
        ticks = [t for _, t, count in gaps for _ in range(count)]
        calls.append((parts, ticks, list(firsts)))
        return note(gaps, firsts)

    eng.stats.note_gaps = logging_note
    return calls


def slow(eng, method: str, seconds: float):
    real = getattr(eng, method)

    def slowed(*args, **kw):
        time.sleep(seconds)
        return real(*args, **kw)

    setattr(eng, method, slowed)


def share_above(hist: dict, bound: float, field: str) -> float:
    top = [b for le, b in hist.items() if le > bound]
    assert top
    return sum(b[field] for b in top) / sum(b["sum_s"] for b in top)


# -- the bucket constant and the histogram alone ------------------------------------


def test_buckets_are_a_quarter_octave_apart_from_1_ms_past_120_s():
    assert GAP_BUCKETS[0] == 1e-3 and GAP_BUCKETS[-1] >= 120.0
    ratios = [b / a for a, b in zip(GAP_BUCKETS, GAP_BUCKETS[1:])]
    assert max(ratios) <= 2 ** 0.25 * (1 + 1e-12) and min(ratios) > 1.18
    assert GAP_PHASES == ("chunk", "build", "device", "scatter", "finish",
                          "sched")


@pytest.mark.parametrize("engine_s, bound", [
    (0.0, 1e-3), (1e-3, 1e-3), (0.00101, GAP_BUCKETS[1]),
    (0.05, GAP_BUCKETS[23]), (100.0, GAP_BUCKETS[67]),
    (131.0, GAP_BUCKETS[68]), (500.0, float("inf")),
])
def test_a_gap_and_a_first_token_are_filed_by_their_engine_seconds(
        engine_s, bound):
    stats = ServingStats("t")
    assert stats.snapshot()["itl"] == {
        "count": 0, "sum_s": 0, "outside_s": 0, "hist": {}}
    assert stats.snapshot()["ttft"]["tail_hist"] == {}
    # the six phases in the account's order, then the time outside, which
    # is kept beside the gap and decides nothing
    row = [engine_s * f for f in (0.5, 0.25, 0.125, 0.0625, 0.03125, 0.03125)]
    stats.note_gaps([(row + [7.0], 1, 1), (row + [0.0], 3, 1)],
                    [(engine_s, 9, 2, engine_s / 2, 0.25)])
    stats.note_gaps([], [(engine_s, 1, 0, 0.0, 0.0)])
    snap = stats.snapshot()
    itl = snap["itl"]
    assert list(itl["hist"]) == [bound]
    assert bound >= engine_s and (bound == 1e-3 or bound / 2 ** 0.25 < engine_s
                                  or bound == float("inf"))
    b = itl["hist"][bound]
    assert (b["count"], b["ticks"], b["outside_s"]) == (2, 4, 7.0)
    assert b["sum_s"] == pytest.approx(2 * engine_s, abs=1e-12)
    assert [b[f] for f in PHASE_FIELDS] == pytest.approx(
        [2 * v for v in row], abs=1e-12)
    assert (itl["count"], itl["outside_s"]) == (2, 7.0)
    assert itl["sum_s"] == b["sum_s"]
    assert snap["ttft"]["tail_hist"] == {bound: {
        "count": 2, "sum_s": 2 * engine_s, "ticks": 10, "unseated_ticks": 2,
        "own_chunk_s": engine_s / 2, "queue_s": 0.25}}
    # what note_ttft keeps is another method's, and is untouched
    assert snap["ttft"]["count"] == 0 and snap["ttft"]["parts"]["queue_s"] == 0


# -- the account and the gaps of a run ----------------------------------------------


RUNS = {
    # 6 sessions on 2 seats of 4 places: chunks, waits for a seat, queueing
    "overcommitted": dict(lengths=(20, 23, 26, 5, 32, 11), new_tokens=6,
                          max_active=4, max_batch=2),
    # everybody seated in every tick, prompts that ride the fused step
    "seated": dict(lengths=(5, 3, 7), new_tokens=12, max_active=4,
                   max_batch=4),
    # a whole-page prompt: the last chunk's token and the step's, one tick
    "two_tokens_a_tick": dict(lengths=(16, 8, 5), new_tokens=5, max_active=4,
                              max_batch=4),
}


@pytest.fixture(scope="module", params=sorted(RUNS))
def served(request, tiny_model):
    """A run to the end: the clients' stamps, every ``note_gaps`` call, the
    snapshot and the engine's account as it stood at the end."""
    kw = dict(RUNS[request.param])
    lengths, new_tokens = kw.pop("lengths"), kw.pop("new_tokens")
    with engine(tiny_model, **kw) as eng:
        calls = gaps_log(eng)
        submit(eng, prompts_of(tiny_model[0], lengths), new_tokens)
        clients = Clients(eng).run()
        return {"clients": clients, "calls": calls, "name": request.param,
                "snap": eng.metrics_meta(), "acct": list(eng._acct),
                "ticks": eng._ticks, "raw_parts": dict(eng.stats.ttft_parts)}


def test_a_gaps_phases_sum_to_its_engine_seconds_and_the_gaps_to_the_wall(
        served):
    itl = served["snap"]["itl"]
    assert itl["count"] > 0
    for bound, b in itl["hist"].items():
        assert sum(b[f] for f in PHASE_FIELDS) == pytest.approx(
            b["sum_s"], abs=1e-6)
        assert b["sum_s"] <= bound * b["count"] + 1e-9
    for field in ("count", "sum_s", "outside_s"):
        assert itl[field] == pytest.approx(
            sum(b[field] for b in itl["hist"].values()), abs=1e-9)
    # Every gap, tick end to tick end: by the moment the engine closed each
    # tick's books to the microsecond, by the caller's stamps after
    # _tick() returned to within what a tick does after that moment.
    clients = served["clients"]
    by_marks = sum(m[-1] - m[0] for m in clients.marks.values() if m)
    by_stamps = sum(s[-1] - s[0] for s in clients.stamps.values() if s)
    assert itl["sum_s"] + itl["outside_s"] == pytest.approx(by_marks, abs=1e-6)
    assert itl["sum_s"] + itl["outside_s"] == pytest.approx(
        by_stamps, rel=0.02, abs=0.05)
    assert 0 <= itl["outside_s"] < itl["sum_s"]
    # one call a tick that made a token, and an account that only grows
    assert 0 < len(served["calls"]) <= served["ticks"]
    assert all(v >= 0 for v in served["acct"])


def test_gaps_are_the_tokens_less_the_sessions_that_emitted(served):
    clients, itl = served["clients"], served["snap"]["itl"]
    assert clients.emitters == len(RUNS[served["name"]]["lengths"])
    assert itl["count"] == clients.tokens - clients.emitters
    noted = [t for _, ticks, _ in served["calls"] for t in ticks]
    assert len(noted) == itl["count"]
    assert sum(noted) == sum(b["ticks"] for b in itl["hist"].values())
    if served["name"] == "seated":
        assert set(noted) == {1}
    elif served["name"] == "overcommitted":
        assert max(noted) > 1 and min(noted) >= 1
    else:
        # the token a prompt's last chunk makes and the one the same tick's
        # fused step makes: a gap of no time and no tick
        assert 0 in noted
        zero = [parts[i] for parts, ticks, _ in served["calls"]
                for i, t in enumerate(ticks) if t == 0]
        assert np.all(np.array(zero) == 0)


def test_first_tokens_are_filed_with_the_wait_they_had(served):
    snap, clients = served["snap"], served["clients"]
    tail = snap["ttft"]["tail_hist"]
    n = len(RUNS[served["name"]]["lengths"])
    assert sum(b["count"] for b in tail.values()) == n == snap["ttft"]["count"]
    for bound, b in tail.items():
        assert 0 <= b["own_chunk_s"] <= b["sum_s"] <= bound * b["count"]
        assert b["ticks"] >= b["count"] and b["unseated_ticks"] <= b["ticks"]
    results = list(clients.results.values())
    assert len(results) == n
    # the session's own count of its unseated ticks, and note_ttft's parts
    # as they were: the sessions' own, to the last bit
    assert sum(b["unseated_ticks"] for b in tail.values()) == sum(
        r.ttft_parts["unseated_ticks"] for r in results)
    for key, total in served["raw_parts"].items():
        assert total == pytest.approx(
            sum(r.ttft_parts[key] for r in results), abs=1e-9)
    assert sum(b["queue_s"] for b in tail.values()) == pytest.approx(
        served["raw_parts"]["queue_s"], abs=1e-9)
    firsts = [f for _, _, fs in served["calls"] for f in fs]
    assert len(firsts) == n
    if served["name"] == "overcommitted":
        assert sum(b["unseated_ticks"] for b in tail.values()) > 0
        assert sum(b["own_chunk_s"] for b in tail.values()) > 0
    if served["name"] == "seated":
        # no whole page of prompt: no chunk was anybody's
        assert all(b["own_chunk_s"] == 0 for b in tail.values())
        assert all(b["unseated_ticks"] == 0 for b in tail.values())


def test_the_snapshot_holds_numbers_only_and_survives_json(served):
    def leaves(node):
        if isinstance(node, dict):
            for v in node.values():
                yield from leaves(v)
        else:
            yield node

    snap = served["snap"]
    for tree in (snap["itl"], snap["ttft"]["tail_hist"]):
        assert all(type(v) in (int, float) for v in leaves(tree)), tree
    assert all(type(k) is float
               for k in list(snap["itl"]["hist"]) + list(snap["ttft"]["tail_hist"]))
    back = json.loads(json.dumps(snap["itl"]))
    assert back["count"] == snap["itl"]["count"]
    assert {float(k) for k in back["hist"]} == set(snap["itl"]["hist"])


def test_the_account_and_the_tracer_read_the_same_spans(tiny_model):
    """The spans are the clock: over a run, each phase of the account is the
    total of the spans summed into it."""
    from oncilla_tpu.utils.debug import GLOBAL_TRACER

    def totals():
        return {op: v["hist"]["sum_s"]
                for op, v in GLOBAL_TRACER.snapshot().items()}

    with engine(tiny_model, max_active=4, max_batch=2) as eng:
        before = totals()
        submit(eng, prompts_of(tiny_model[0], (20, 9, 26, 5)), 6)
        Clients(eng).run()
        after = totals()
        acct = dict(zip(GAP_PHASES + ("outside",), eng._acct))
        (last_tick, last_finish), mark_t = eng._last_tick, eng._mark_t

    def spans(*ops):
        return sum(after.get(op, 0.0) - before.get(op, 0.0) for op in ops)

    assert acct["chunk"] == pytest.approx(spans("serve_prefill_chunk"), abs=1e-9)
    assert acct["build"] == pytest.approx(
        spans("step.residency", "step.args", "step.carry", "step.pool"),
        abs=1e-9)
    assert acct["device"] == pytest.approx(
        spans("step.dispatch", "step.sync"), abs=1e-9)
    assert acct["scatter"] == pytest.approx(spans("step.scatter"), abs=1e-9)
    # finish and sched lack what the last tick did after it closed its
    # books, which no later tick was there to see
    late = last_tick.t0 + last_tick.dt - mark_t
    assert 0 < late < 0.5
    assert acct["finish"] == pytest.approx(
        spans("tick.finish") - (last_finish.t0 + last_finish.dt - mark_t),
        abs=1e-9)
    assert sum(acct[p] for p in GAP_PHASES) == pytest.approx(
        spans("tick") - late, abs=1e-9)


def test_a_session_that_loses_its_seat_for_a_tick_files_a_gap_of_two_ticks(
        tiny_model):
    cfg, _ = tiny_model
    with engine(tiny_model, max_active=2, max_batch=1) as eng:
        calls = gaps_log(eng)
        submit(eng, prompts_of(cfg, (5,)), new_tokens=10)
        clients = Clients(eng)
        while len(clients.stamps.get("t0", [])) < 3:
            clients.tick()
        # A one-token prompt of a higher class: it takes the one seat for
        # the one tick that makes its one token.
        submit(eng, prompts_of(cfg, (1,), seed=1), new_tokens=1, first=1,
               priority=PRIO_HIGH)
        clients.run()
    assert len(clients.stamps["t0"]) == 10 and len(clients.stamps["t1"]) == 1
    noted = [t for _, ticks, _ in calls for t in ticks]
    assert sorted(noted) == [1] * 8 + [2]
    hist = eng.stats.snapshot()["itl"]["hist"]
    assert sum(b["ticks"] for b in hist.values()) == 10
    # t1 waited for nothing: admitted, seated and served in one tick
    (first,) = [f for _, _, fs in calls for f in fs if f[1] == 1]
    assert first[2] == 0 and first[3] == 0.0


@pytest.mark.parametrize("method, field, lengths", [
    # a decode crosses a page boundary: the ship is in step.scatter
    ("_ship", "scatter_s", (5, 6)),
    # one session decodes while another's long prompt goes a chunk a tick
    ("_prefill_chunk", "chunk_s", (2, 48)),
])
def test_a_slow_phase_is_most_of_the_tail(tiny_model, method, field, lengths):
    cfg, _ = tiny_model
    with engine(tiny_model, max_active=2, max_batch=2) as eng:
        # every program built before anything is slowed or filed
        submit(eng, prompts_of(cfg, lengths), new_tokens=12)
        Clients(eng).run()
        before = eng.stats.snapshot()["itl"]["hist"]
        slow(eng, method, 0.1)
        submit(eng, prompts_of(cfg, lengths, seed=2), new_tokens=12)
        Clients(eng).run()
        hist = eng.stats.snapshot()["itl"]["hist"]
    window = {le: {k: v - before.get(le, {}).get(k, 0) for k, v in b.items()}
              for le, b in hist.items()}
    window = {le: b for le, b in window.items() if b["count"]}
    assert share_above(window, 0.08, field) > 0.8
    others = [f for f in PHASE_FIELDS if f != field]
    assert all(share_above(window, 0.08, f) < 0.2 for f in others)


def test_two_engines_in_one_process_keep_separate_accounts(tiny_model):
    cfg, _ = tiny_model
    with engine(tiny_model, name="a", max_active=2, max_batch=2) as a, \
            engine(tiny_model, name="b", max_active=2, max_batch=2) as b:
        submit(a, prompts_of(cfg, (5, 12)), new_tokens=8)
        submit(b, prompts_of(cfg, (7,), seed=3), new_tokens=5)
        ca, cb = Clients(a), Clients(b)
        while a.queue or a.active or b.queue or b.active:
            for c in (ca, cb):
                if c.eng.queue or c.eng.active:
                    c.tick()
        sa, sb = a.stats.snapshot()["itl"], b.stats.snapshot()["itl"]
        acct_a, acct_b = list(a._acct), list(b._acct)
    assert a._acct is not b._acct and a.stats is not b.stats
    assert sa["count"] == ca.tokens - 2 and sb["count"] == cb.tokens - 1
    assert (ca.tokens, cb.tokens) == (16, 5)
    # while they alternated, each one's ticks were the other's time outside
    engine_b = sum(acct_b[:len(GAP_PHASES)])
    assert acct_a[-1] >= 0.5 * engine_b > 0
    assert sa["outside_s"] > 0 and sb["outside_s"] > 0
    for itl, clients in ((sa, ca), (sb, cb)):
        by_marks = sum(m[-1] - m[0] for m in clients.marks.values())
        assert itl["sum_s"] + itl["outside_s"] == pytest.approx(
            by_marks, abs=1e-6)
