"""Cluster metrics history (obs/scrape.py) + SLO engine (obs/slo.py).

Unit layers feed the history synthetic samples with explicit timestamps
so every windowed delta/rate/quantile/burn figure is deterministic; the
integration layer runs the real thing — STATUS_PROM scrapes over an
in-process cluster, a seeded slow handler tripping the burn alert, the
``ocm_slo_*`` exposition holding the same validation bar as every other
renderer.
"""

import numpy as np
import pytest

from oncilla_tpu.obs import journal, prom, scrape, slo
from oncilla_tpu.runtime.cluster import local_cluster
from oncilla_tpu.utils.config import OcmConfig

from oncilla_tpu import OcmKind


def _cfg(**kw) -> OcmConfig:
    base = dict(
        host_arena_bytes=8 << 20,
        device_arena_bytes=1 << 20,
        chunk_bytes=128 << 10,
        heartbeat_s=5.0,
    )
    base.update(kw)
    return OcmConfig(**base)


@pytest.fixture
def journaling():
    was = journal.enabled()
    journal.set_enabled(True)
    journal.clear()
    yield journal
    journal.set_enabled(was)
    journal.clear()


# -- exposition parsing --------------------------------------------------


def test_parse_samples_roundtrip_with_labels_and_exemplars():
    doc = prom._Doc()
    doc.sample("ocm_op_total", "counter", "ops", 7, rank=0, op="dcn_put")
    doc.sample("ocm_op_total", "counter", "ops", 3, rank=1, op="dcn_get")
    fam = "ocm_op_latency_seconds"
    doc.sample(fam, "histogram", "lat", 5, name=fam + "_bucket",
               exemplar=' # {trace_id="00ff"} 0.004 1.0',
               rank=0, op="dcn_put", le="0.005")
    out = scrape.parse_samples(doc.text())
    by_name = {}
    for family, name, labels, value in out:
        by_name.setdefault(name, []).append((family, labels, value))
    assert ("ocm_op_total", {"rank": "0", "op": "dcn_put"}, 7.0) \
        in by_name["ocm_op_total"]
    # The exemplar is stripped before the value parse.
    family, labels, value = by_name[fam + "_bucket"][0]
    assert family == fam and labels["le"] == "0.005" and value == 5.0


def test_parse_samples_rejects_malformed_exposition():
    with pytest.raises(ValueError):
        scrape.parse_samples("ocm_bad{ 1.0\n")


def test_scrape_interval_env_tolerant(monkeypatch):
    monkeypatch.setenv(scrape.ENV_SCRAPE_S, "0.25")
    assert scrape.scrape_interval_s() == 0.25
    monkeypatch.setenv(scrape.ENV_SCRAPE_S, "banana")
    assert scrape.scrape_interval_s() == scrape.DEFAULT_SCRAPE_S


# -- history rings -------------------------------------------------------


def _feed(h: scrape.MetricsHistory, ts: float, value: float,
          name: str = "ocm_op_total", **labels) -> None:
    labels = {k: str(v) for k, v in labels.items()} or {"rank": "0"}
    h.observe_samples([(name, name, labels, value)], ts=ts)


def test_delta_and_rate_windowed():
    h = scrape.MetricsHistory()
    for ts, v in ((0.0, 100.0), (10.0, 120.0), (20.0, 150.0)):
        _feed(h, ts, v)
    assert h.delta("ocm_op_total", 30.0, now=20.0) == 50.0
    # A window starting after the first sample only sees the later rise.
    assert h.delta("ocm_op_total", 11.0, now=20.0) == 30.0
    assert h.rate("ocm_op_total", 10.0, now=20.0) == pytest.approx(3.0)


def test_delta_is_counter_reset_aware():
    h = scrape.MetricsHistory()
    # 100 -> 120 (+20), restart to 5 (+5), -> 15 (+10): increase = 35.
    for ts, v in ((0.0, 100.0), (1.0, 120.0), (2.0, 5.0), (3.0, 15.0)):
        _feed(h, ts, v)
    assert h.delta("ocm_op_total", 10.0, now=3.0) == 35.0


def test_delta_aggregates_across_label_sets_with_subset_match():
    h = scrape.MetricsHistory()
    for ts in (0.0, 1.0):
        _feed(h, ts, 10.0 * (ts + 1), rank=0, op="a")
        _feed(h, ts, 2.0 * (ts + 1), rank=1, op="a")
        _feed(h, ts, 100.0 * (ts + 1), rank=0, op="b")
    assert h.delta("ocm_op_total", 5.0, now=1.0, op="a") == 12.0
    assert h.delta("ocm_op_total", 5.0, now=1.0) == 112.0
    assert h.latest("ocm_op_total", rank="1") == 4.0
    assert h.latest("ocm_op_total", rank="9") is None


def test_ring_cap_keeps_newest():
    h = scrape.MetricsHistory(cap=4)
    for i in range(10):
        _feed(h, float(i), float(i))
    (ring,) = h.series("ocm_op_total").values()
    assert [t for t, _ in ring] == [6.0, 7.0, 8.0, 9.0]
    assert h.meta()["cap"] == 4


def test_hist_quantile_from_windowed_bucket_deltas():
    h = scrape.MetricsHistory()
    fam = "ocm_op_latency_seconds"

    def feed_hist(ts: float, cums: dict) -> None:
        for le, cum in cums.items():
            h.observe_samples(
                [(fam, fam + "_bucket", {"rank": "0", "le": le}, cum)],
                ts=ts,
            )

    feed_hist(0.0, {"0.01": 100, "0.1": 100, "+Inf": 100})
    # Window adds 80 obs <= 10 ms and 20 in (10 ms, 100 ms].
    feed_hist(10.0, {"0.01": 180, "0.1": 200, "+Inf": 200})
    q50 = h.hist_quantile(fam, 0.50, 15.0, now=10.0)
    assert q50 is not None and 0.0 < q50 <= 0.01
    q95 = h.hist_quantile(fam, 0.95, 15.0, now=10.0)
    assert q95 == pytest.approx(0.01 + (0.95 * 100 - 80) / 20 * 0.09)
    assert h.hist_quantile(fam, 0.5, 15.0, now=10.0, rank="7") is None


def test_scraper_poll_once_counts_fetch_errors():
    h = scrape.MetricsHistory()
    doc = prom._Doc()
    doc.sample("ocm_nnodes", "gauge", "n", 2, rank=0)
    text = doc.text()

    def fetch(rank: int) -> str:
        if rank == 1:
            raise ConnectionRefusedError("down")
        return text

    s = scrape.Scraper(fetch, range(2), history=h, interval_s=60.0)
    assert s.poll_once(ts=1.0) == 1
    assert h.meta()["errors"] == 1
    assert h.latest("ocm_nnodes") == 2.0


# -- objectives / spec loading ------------------------------------------


def test_default_objectives_scale_with_budget():
    objs = {o.name: o for o in slo.default_objectives(budget_s=2.0)}
    assert objs["latency_high"].threshold_s == pytest.approx(1.0)
    assert objs["latency_normal"].threshold_s == pytest.approx(2.0)
    assert objs["latency_low"].threshold_s == pytest.approx(4.0)
    assert objs["availability"].kind == "availability"
    assert objs["serving_tokens"].kind == "throughput"


def test_load_spec_env_shapes(monkeypatch, tmp_path):
    monkeypatch.setenv(slo.ENV_SLO, "off")
    assert slo.load_spec() is None
    monkeypatch.setenv(slo.ENV_SLO, "1")
    objectives, fast, _slow, _thr = slo.load_spec(budget_s=1.0)
    assert {o.name for o in objectives} >= {"latency_high", "availability"}
    assert fast == slo.DEFAULT_FAST_S
    spec = tmp_path / "slo.json"
    spec.write_text(
        '{"fast_s": 5, "slow_s": 25, "burn_threshold": 3,'
        ' "objectives": [{"name": "x", "kind": "throughput",'
        '  "family": "ocm_serving_tokens_total", "min_rate": 2.5}]}'
    )
    monkeypatch.setenv(slo.ENV_SLO, str(spec))
    objectives, fast, slow, thr = slo.load_spec()
    assert [o.name for o in objectives] == ["x"]
    assert (fast, slow, thr) == (5.0, 25.0, 3.0)
    # A typo'd spec degrades to the defaults, never raises.
    monkeypatch.setenv(slo.ENV_SLO, "{not json")
    objectives, _f, _s, _t = slo.load_spec(budget_s=1.0)
    assert {o.name for o in objectives} >= {"latency_high"}


def test_unknown_objective_kind_rejected():
    with pytest.raises(ValueError):
        slo.Objective("bad", "vibes")


# -- engine verdicts -----------------------------------------------------


def _lat_hist(h: scrape.MetricsHistory, ts: float, fast: int, slow: int,
              rank: str = "0") -> None:
    """One scrape of a cumulative latency histogram: ``fast`` obs <= 1 ms,
    ``slow`` obs in the +Inf tail."""
    fam = "ocm_op_latency_seconds"
    for le, cum in (("0.001", fast), ("+Inf", fast + slow)):
        h.observe_samples(
            [(fam, fam + "_bucket", {"rank": rank, "le": le}, cum)], ts=ts
        )


def test_engine_healthy_green_with_idle_objectives_ok(journaling):
    h = scrape.MetricsHistory()
    _lat_hist(h, 0.0, fast=0, slow=0)
    _lat_hist(h, 5.0, fast=100, slow=0)
    eng = slo.SloEngine(
        h, slo.default_objectives(budget_s=1.0), fast_s=10.0, slow_s=20.0
    )
    result = eng.evaluate(now=5.0)
    assert result["ok"]
    by_name = {v["objective"]: v for v in result["objectives"]}
    assert by_name["latency_high"]["active"]
    assert not by_name["serving_tokens"]["active"]
    assert by_name["serving_tokens"]["ok"]
    assert not any(e["ev"] == "slo_burn" for e in journal.events())


def test_engine_burn_requires_both_windows(journaling):
    h = scrape.MetricsHistory()
    # Old healthy traffic fills the slow window; the errors are recent.
    _lat_hist(h, 0.0, fast=0, slow=0)
    _lat_hist(h, 80.0, fast=1000, slow=0)
    _lat_hist(h, 95.0, fast=1000, slow=40)
    eng = slo.SloEngine(
        h, slo.default_objectives(budget_s=1.0), fast_s=20.0, slow_s=100.0
    )
    result = eng.evaluate(now=95.0)
    by_name = {v["objective"]: v for v in result["objectives"]}
    v = by_name["latency_normal"]
    # Fast window: 40/40 errors (burn 100x); slow window: 40/1040 (~3.8x).
    assert v["burn_fast"] > v["burn_slow"] > eng.burn_threshold
    assert not v["ok"] and not result["ok"]
    # Same shape but with enough recent healthy traffic that the slow
    # window stays under threshold: no alert (the single-bad-scrape
    # guard).
    h2 = scrape.MetricsHistory()
    _lat_hist(h2, 0.0, fast=0, slow=0)
    _lat_hist(h2, 80.0, fast=10000, slow=0)
    _lat_hist(h2, 95.0, fast=10000, slow=40)
    eng2 = slo.SloEngine(
        h2, slo.default_objectives(budget_s=1.0), fast_s=20.0, slow_s=100.0
    )
    r2 = eng2.evaluate(now=95.0)
    assert {v["objective"]: v for v in r2["objectives"]}[
        "latency_normal"]["ok"]


def test_engine_burn_and_recovery_journal_events(journaling):
    h = scrape.MetricsHistory()
    _lat_hist(h, 0.0, fast=0, slow=0)
    _lat_hist(h, 5.0, fast=10, slow=90)
    eng = slo.SloEngine(
        h, slo.default_objectives(budget_s=1.0), fast_s=10.0, slow_s=20.0
    )
    assert not eng.evaluate(now=5.0)["ok"]
    burns = [e for e in journal.events() if e["ev"] == "slo_burn"]
    assert burns and burns[0]["objective"].startswith("latency_")
    # Recovery: the errored window ages out, fresh healthy traffic only.
    _lat_hist(h, 100.0, fast=10, slow=90)
    _lat_hist(h, 105.0, fast=500, slow=90)
    ok = eng.evaluate(now=105.0)
    assert ok["ok"]
    oks = [e for e in journal.events() if e["ev"] == "slo_ok"]
    assert {e["objective"] for e in oks} == {
        e["objective"] for e in burns
    }
    # Steady green does not re-emit slo_ok (transition event only).
    eng.evaluate(now=106.0)
    assert len([e for e in journal.events() if e["ev"] == "slo_ok"]) \
        == len(oks)


def test_availability_objective_counts_typed_errors():
    h = scrape.MetricsHistory()
    for ts, total, busy in ((0.0, 0, 0), (5.0, 1000, 30)):
        _feed(h, ts, total, name="ocm_op_total", rank=0)
        _feed(h, ts, busy, name="ocm_backpressure_busy_total", rank=0)
    eng = slo.SloEngine(
        h, slo.default_objectives(budget_s=1.0), fast_s=10.0, slow_s=20.0
    )
    v = {o["objective"]: o for o in eng.evaluate(now=5.0)["objectives"]}
    # 30/1000 against a 99.9% target: burn 30x in both windows.
    assert not v["availability"]["ok"]
    assert v["availability"]["burn_fast"] == pytest.approx(30.0, rel=0.01)


def test_throughput_objective_idle_vs_starved():
    h = scrape.MetricsHistory()
    eng = slo.SloEngine(
        h, slo.default_objectives(budget_s=1.0), fast_s=10.0, slow_s=20.0
    )
    fam = "ocm_serving_tokens_total"
    # Idle stream: no samples at all -> inactive, ok.
    v = {o["objective"]: o for o in eng.evaluate(now=5.0)["objectives"]}
    assert v["serving_tokens"]["ok"] and not v["serving_tokens"]["active"]
    # Active but starved: tokens trickle far under min_rate.
    _feed(h, 0.0, 0.0, name=fam, rank=0, phase="decode")
    _feed(h, 5.0, 2.0, name=fam, rank=0, phase="decode")
    v = {o["objective"]: o for o in eng.evaluate(now=5.0)["objectives"]}
    assert v["serving_tokens"]["active"] and not v["serving_tokens"]["ok"]


def test_render_prom_validates_and_carries_verdicts(journaling):
    h = scrape.MetricsHistory()
    _lat_hist(h, 0.0, fast=0, slow=0)
    _lat_hist(h, 5.0, fast=10, slow=90)
    eng = slo.SloEngine(
        h, slo.default_objectives(budget_s=1.0), fast_s=10.0, slow_s=20.0
    )
    eng.evaluate(now=5.0)
    text = eng.render_prom(rank=0)
    fams = prom.validate(text)
    assert {"ocm_slo_ok", "ocm_slo_target", "ocm_slo_burn_rate",
            "ocm_slo_error_ratio", "ocm_slo_evaluations_total"} \
        <= set(fams)
    assert any(
        'objective="latency_high"' in line and line.endswith(" 0")
        for line in fams["ocm_slo_ok"]
    )
    assert any('window="fast"' in line for line in fams["ocm_slo_burn_rate"])


def test_runner_injects_extra_samples(journaling):
    doc = prom._Doc()
    doc.sample("ocm_op_total", "counter", "ops", 1, rank=0, op="a")
    text = doc.text()
    calls = {"n": 0}

    def extra():
        calls["n"] += 1
        return [("ocm_client_breaker_opens_total",
                 "ocm_client_breaker_opens_total", {"rank": "0"},
                 float(calls["n"]))]

    runner = slo.SloRunner(
        lambda rank: text, range(1), objectives=slo.default_objectives(1.0),
        interval_s=60.0,
    )
    runner.extra_samples = extra
    runner.tick(ts=1.0)
    runner.tick(ts=2.0)
    assert runner.history.latest("ocm_client_breaker_opens_total") == 2.0
    meta = runner.meta()
    assert meta["evaluations"] == 2 and meta["history"]["scrapes"] >= 2


# -- integration: real cluster, real burn -------------------------------


def test_client_slo_watcher_surfaces_in_status(journaling, monkeypatch):
    monkeypatch.delenv(slo.ENV_SLO, raising=False)
    with local_cluster(2, config=_cfg()) as c:
        ctx = c.context(0, heartbeat=False)
        data = np.arange(32 << 10, dtype=np.uint8)
        for _ in range(4):
            h = ctx.alloc(len(data), OcmKind.REMOTE_HOST)
            try:
                ctx.put(h, data)
                np.asarray(ctx.get(h))
            finally:
                ctx.free(h)
        runner = ctx.start_slo(interval_s=60.0)
        assert runner is not None
        assert ctx.start_slo() is runner  # idempotent
        runner.tick()
        runner.tick()
        block = ctx.status()["slo"]
        assert block["ok"] and block["evaluations"] >= 2
        assert block["history"]["series"] > 0
        names = {v["objective"] for v in block["objectives"]}
        assert {"latency_high", "availability"} <= names
        ctx.stop_slo()


def test_slo_disabled_by_env(monkeypatch):
    monkeypatch.setenv(slo.ENV_SLO, "0")
    assert slo.SloRunner.from_env(lambda r: "", range(1)) is None


def test_seeded_slow_handler_trips_burn(journaling):
    """The CI burn fixture's core: a handler_delay_s past the high-QoS
    latency bound must flip the healthy verdict to BURNING."""
    from oncilla_tpu.runtime.protocol import MsgType

    with local_cluster(2, config=_cfg()) as c:
        ctx = c.context(0, heartbeat=False)
        runner = slo.SloRunner(
            ctx.fetch_prom, range(2),
            objectives=slo.default_objectives(budget_s=0.2),
            interval_s=60.0, fast_s=8.0, slow_s=16.0,
        )
        data = np.arange(32 << 10, dtype=np.uint8)

        def burst(n: int) -> None:
            for _ in range(n):
                h = ctx.alloc(len(data), OcmKind.REMOTE_HOST)
                try:
                    ctx.put(h, data)
                    np.asarray(ctx.get(h))
                finally:
                    ctx.free(h)

        burst(5)
        runner.tick()
        burst(5)
        assert runner.tick()["ok"]
        for d in c.daemons:
            d.handler_delay_types = frozenset(
                {MsgType.DATA_PUT, MsgType.DATA_GET}
            )
            d.handler_delay_s = 0.15
        try:
            burst(3)
        finally:
            for d in c.daemons:
                d.handler_delay_s = 0.0
                d.handler_delay_types = frozenset()
        burning = runner.tick()
        assert not burning["ok"]
        tripped = {
            v["objective"] for v in burning["objectives"] if not v["ok"]
        }
        assert "latency_high" in tripped
        assert any(e["ev"] == "slo_burn" for e in journal.events())
        assert "ocm_slo_ok" in prom.validate(runner.engine.render_prom(0))


# -- serving TTFT metric -------------------------------------------------


def test_serving_ttft_histogram_renders_and_validates():
    from oncilla_tpu.serving.metrics import ServingStats

    st = ServingStats("eng")
    st.note_ttft(0.003)
    st.note_ttft(0.3)
    snap = st.snapshot()
    assert snap["ttft"]["count"] == 2
    assert snap["ttft"]["hist"][0.005] == 1
    text = prom.render_serving({"engines": [snap]}, rank=0)
    fams = prom.validate(text)
    fam = "ocm_serving_ttft_seconds"
    assert fam in fams
    bucket_lines = [ln for ln in fams[fam] if "_bucket" in ln]
    assert any('le="+Inf"' in ln and ln.endswith(" 2")
               for ln in bucket_lines)


def test_serving_itl_histogram_renders_cumulative_and_validates():
    from oncilla_tpu.serving.metrics import GAP_BUCKETS, ServingStats

    st = ServingStats("eng")
    assert "ocm_serving_itl_seconds" not in prom.render_serving(
        {"engines": [st.snapshot()]}, rank=0)
    # gaps of 0.8 ms, 3 ms, 3.1 ms, 70 ms and one beyond the last bound, all
    # device time but for the last, which stood 2 s outside the engine too
    def gap(device_s, outside_s=0.0):
        return [0.0, 0.0, device_s, 0.0, 0.0, 0.0, outside_s]

    st.note_gaps([(gap(0.0008), 1, 1), (gap(0.003), 1, 1), (gap(0.0031), 1, 1),
                  (gap(0.07), 2, 1), (gap(200.0, 2.0), 40, 1)])
    snap = st.snapshot()
    assert snap["itl"]["count"] == 5 and snap["itl"]["outside_s"] == 2.0
    text = prom.render_serving({"engines": [snap]}, rank=0)
    fams = prom.validate(text)
    fam = "ocm_serving_itl_seconds"
    buckets = {}
    for ln in fams[fam]:
        if "_bucket" in ln:
            le = ln.split('le="')[1].split('"')[0]
            buckets[float(le.replace("+Inf", "inf"))] = int(ln.rsplit(" ", 1)[1])
    # only the bounds that hold a gap, the program's own, counted from below
    assert sorted(buckets.values()) == [1, 3, 4, 5]
    assert set(buckets) - {float("inf")} < set(GAP_BUCKETS)
    assert buckets[0.001] == 1 and buckets[float("inf")] == 5
    assert any(ln.startswith(fam + "_sum") and
               float(ln.rsplit(" ", 1)[1]) == pytest.approx(200.0769)
               for ln in fams[fam])
    assert any(ln.startswith(fam + "_count") and ln.endswith(" 5")
               for ln in fams[fam])
