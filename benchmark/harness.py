"""One run of one cell: build the serving stack, warm it, offer the cell's
traffic for ``--seconds``, check the outputs, print the contract's line.

Nothing here names a cell, a model or a metric. A cell is an entry of
``BENCHMARK.json``'s ``workloads``; its configuration is the file the
``configs`` entry names, which names its model family
(``families/<family>.py``: the adapter to the program's model module, which
in turn names the family's plain reference, ``references/<name>.py``, and
its bytes model, ``bytes_models/<name>.py``); its traffic mix is
``traffic/<traffic>.json``, which names its generator
(``generators/<name>.py``) and its warmer (``warmers/<name>.py``); each
per-layer metric is ``layer_metrics/<name>.py``. See ``README.md`` beside
this file.

From the program this takes the system under test (``ServingEngine`` over
``TieredPageStore``, ``PrefixCache``, ``Ocm`` and an in-process COLD
cluster, built through their public constructors), its counters
(``ServingStats.snapshot()``), its spans (``GLOBAL_TRACER.snapshot()``) and
its program and kernel names in the profiler's trace. Clocks, percentiles,
traffic, the reference and the verdict are the benchmark's own.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Requests the reference is compared on, after the window: further ones run
# through the same engine with its logits kept, and a sample of those the
# window itself finished (the longest among them), by their tokens.
CHECK_REQUESTS = 2
SERVED_REQUESTS = 8
# Profiled slice of a traced run's window: long enough for some tens of
# ticks, short enough for the trace to stay small.
TRACE_AFTER_S = 2.0
TRACE_SECONDS = 3.0
# Ceiling on ticks spent draining after the window (a wedged engine fails
# the run instead of hanging it).
DRAIN_TICKS = 20000


class Refused(Exception):
    """The run cannot start here (no TPU, too few chips, unknown cell)."""


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


_loaded: dict = {}


def load_plugin(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by name (``kind``
    empty: a file beside this one). Loaded by path, so a copy of the
    benchmark elsewhere runs its own files."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if path not in _loaded:
        if not os.path.isfile(path):
            raise Refused(f"no {kind or 'module'} named {name!r}: "
                          f"{path} is missing")
        spec = importlib.util.spec_from_file_location(
            "benchmark_" + re.sub(r"\W", "_", f"{kind}_{name}"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod    # dataclasses look their module up
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    end_to_end: list    # the metric entries this cell reports
    per_layer: list


def load_cell(workload: str) -> Cell:
    bench = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_read_json(os.path.join(ROOT, conf["file"])),
        traffic=_read_json(os.path.join(
            HERE, "traffic", f"{w['traffic']}.json")),
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]),
    )


@dataclasses.dataclass
class Family:
    """What depends on the architecture, found through the configuration's
    ``family`` key."""

    name: str
    adapter: object      # families/<family>.py
    reference: object    # references/<adapter.REFERENCE>.py
    bytes_model: object  # bytes_models/<adapter.BYTES_MODEL>.py


def load_family(conf: dict) -> Family:
    """The configuration's model family. A configuration that names none, or
    one with no adapter, is refused: no family is the default."""
    name = conf.get("family")
    if not name:
        raise Refused(f"configuration {conf.get('name')!r} names no model "
                      "family (its file needs a \"family\" key)")
    adapter = load_plugin("families", name)
    return Family(
        name=name, adapter=adapter,
        reference=load_plugin("references", adapter.REFERENCE),
        bytes_model=load_plugin("bytes_models", adapter.BYTES_MODEL))


def seeded_weights(family: Family, conf: dict, seed: int):
    """The program's config and the weights of a run: made on the device in
    one jitted call from the seed's key, in the type they are served in."""
    import jax

    cfg = family.adapter.program_config(conf)
    params = jax.jit(lambda key: family.adapter.init_params(key, cfg))(
        jax.random.key(seed))
    return cfg, jax.block_until_ready(params)


class CompileMeter:
    """Executables the backend built or fetched, and the seconds JAX spent
    tracing and lowering, from JAX's own monitoring events (as
    ``chip_smoke.py::CompileMeter``)."""

    _TRACE = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax.monitoring

        self.executables = 0
        self.backend_s = 0.0
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.backend_s += duration_secs
        elif event in self._TRACE:
            self.trace_lower_s += duration_secs

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self) -> dict:
        return {"executables": self.executables,
                "backend_s": round(self.backend_s, 2),
                "trace_lower_s": round(self.trace_lower_s, 2),
                "cache_hits": self.cache_hits}


# -- the serving stack ---------------------------------------------------------


def _cluster_config(host_arena_bytes: int):
    """The COLD cluster: two replicas of every page, ``chip_smoke.py``'s data
    plane (one stripe, 256 KiB chunks, proven on the chip) and ``OcmConfig``'s
    own lease, heartbeat and failure-detector timings. The smoke's
    chaos-test timings (a daemon DEAD after two 0.25 s probes, 5 s leases)
    declared a live in-process daemon dead under this load in one run of
    eight (PERF.md section 6, PR 24)."""
    from oncilla_tpu.utils.config import OcmConfig

    return OcmConfig(
        host_arena_bytes=host_arena_bytes, device_arena_bytes=4 << 20,
        replicas=2, dcn_stripes=1, chunk_bytes=256 << 10,
    )


@contextlib.contextmanager
def serving_stack(cfg, params, eng: dict, name: str, problems: list):
    """Engine, tier store, prefix cache, memory plane and COLD cluster,
    composed as ``serving/__main__.py::_build_engine`` composes them. At exit
    everything is closed and the drain guarantees are checked into
    ``problems``."""
    import oncilla_tpu as ocm
    from oncilla_tpu.qos.policy import PRIO_LOW
    from oncilla_tpu.runtime.client import ControlPlaneClient
    from oncilla_tpu.runtime.cluster import local_cluster
    from oncilla_tpu.serving.engine import ServingEngine
    from oncilla_tpu.serving.metrics import ServingStats
    from oncilla_tpu.serving.prefix import PrefixCache
    from oncilla_tpu.serving.tiers import TieredPageStore

    page_tokens = int(eng["page_tokens"])
    page_bytes = ServingEngine.page_nbytes(cfg, page_tokens)
    slot = max(page_bytes, 4096)
    hot, warm = int(eng["hot_pages"]), int(eng["warm_pages"])
    daemons = int(eng["cold_daemons"])
    # Every COLD page lives on two of the daemons; capacity placement
    # spreads them, so each arena is sized for its share with half to spare.
    per_daemon = -(-int(eng["cold_pages"]) * 2 * 3 // (2 * daemons)) + 4
    cluster_cfg = _cluster_config(max(32 << 20, per_daemon * slot))
    with local_cluster(daemons, config=cluster_cfg) as cl:
        cold = ControlPlaneClient(
            cl.entries, 0,
            config=dataclasses.replace(cl.config, priority=PRIO_LOW))
        ctx = ocm.Ocm(config=ocm.OcmConfig(
            host_arena_bytes=max((warm + 4) * slot, 1 << 20),
            device_arena_bytes=max((hot + 4) * slot, 1 << 20),
        ))
        store = TieredPageStore(
            ctx, page_bytes, hot_capacity=hot, warm_capacity=warm,
            cold_backend=cold, stats=ServingStats(name),
        )
        prefix = (PrefixCache(store, page_tokens)
                  if eng["prefix_cache"] else None)
        engine = ServingEngine(
            params, cfg, store, prefix, page_tokens=page_tokens,
            max_active=int(eng["max_active"]),
            max_batch=int(eng["max_batch"]), batched=True,
            prefetch_workers=int(eng["prefetch_workers"]), name=name,
        )
        try:
            yield engine, page_bytes
        finally:
            hot_left = warm_left = -1
            try:
                engine.close()
                store.close()
                hot_left = ctx.device_arenas[0].allocator.bytes_live
                warm_left = ctx.host_arena.allocator.bytes_live
                ctx.tini()
                cold.close()
            except Exception as e:  # the one boundary: a failed close is a
                # verdict (the line says "correct": false), not a lost run
                traceback.print_exc()
                problems.append(f"close failed: {type(e).__name__}: {e}")
            if hot_left or warm_left:
                problems.append(f"arenas not drained at close: device "
                                f"{hot_left} B, host {warm_left} B")
            left = _undrained(cl, 30.0 if hot_left == 0 else 2.0)
            if left:
                problems.append(left)


def _undrained(cl, wait_s: float = 30.0) -> str | None:
    """A COLD daemon that still holds an allocation after close."""
    deadline = time.monotonic() + wait_s
    while True:
        msg = None
        for d in cl.daemons:
            if d.registry.live_count() or d.host_arena.allocator.bytes_live:
                msg = (f"COLD rank {d.rank} not drained: "
                       f"{d.registry.live_count()} live allocations")
        if msg is None or time.monotonic() > deadline:
            return msg
        time.sleep(0.1)


# -- the client's side ------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """One request as its client saw it."""

    index: int
    submit_t: float
    want: int
    prompt_len: int
    stamps: list = dataclasses.field(default_factory=list)
    out: list | None = None        # the tokens, once it finished
    result: object = None


class Loop:
    """Drives the engine one tick at a time and is every client at once:
    submits, stamps each session's new tokens after the tick that made them
    (a tick ends in the arg-max's host sync) and collects what finished."""

    def __init__(self, engine, sched: dict, clock=time.perf_counter):
        from oncilla_tpu.serving.engine import Request

        self._Request = Request
        self.engine = engine
        self.nth = sched["nth"]
        self.clients = int(sched["clients"])
        self.clock = clock
        self.next_index = 0
        self.inflight: dict[str, Rec] = {}
        self.done: list[Rec] = []
        self.ticks = 0
        self.start_t = clock()
        self.accepting = True
        self._due: dict | None = None   # open loop: the next request, held

    def _submit(self, req: dict, submit_t: float) -> None:
        i = self.next_index
        self.next_index += 1
        tenant = f"q{i}"
        self.inflight[tenant] = Rec(
            index=i, submit_t=submit_t, want=int(req["max_new_tokens"]),
            prompt_len=len(req["tokens"]))
        self.engine.submit(self._Request(
            tenant=tenant, tokens=req["tokens"],
            max_new_tokens=int(req["max_new_tokens"])))

    def offer(self) -> float | None:
        """Submit what is due. Returns the seconds until the next arrival
        when the engine has nothing to do (an idle open loop)."""
        if not self.accepting:
            return None
        if self.clients:
            while len(self.inflight) < self.clients:
                self._submit(self.nth(self.next_index), self.clock())
            return None
        while True:
            if self._due is None:
                self._due = self.nth(self.next_index)
            due_t = self.start_t + self._due["at_s"]
            now = self.clock()
            if due_t > now:
                return due_t - now
            # Timed from when it was due, so a late generator shows as wait.
            self._submit(self._due, due_t)
            self._due = None

    def tick(self) -> None:
        wait = self.offer()
        eng = self.engine
        if not (eng.queue or eng.active):
            if wait is not None:
                time.sleep(min(wait, 0.05))
            return
        eng._tick()
        self.ticks += 1
        now = self.clock()
        for sess in eng.active:
            rec = self.inflight[sess.req.tenant]
            rec.stamps.extend([now] * (len(sess.out) - len(rec.stamps)))
        if eng.results:
            finished, eng.results = eng.results, []
            for res in finished:
                rec = self.inflight.pop(res.tenant)
                rec.stamps.extend(
                    [now] * (len(res.out_tokens) - len(rec.stamps)))
                rec.out = list(res.out_tokens)
                rec.result = res
                self.done.append(rec)

    def run_until(self, stop) -> None:
        while not stop():
            self.tick()

    def drain(self) -> None:
        self.accepting = False
        ticks0 = self.ticks
        while self.engine.queue or self.engine.active:
            if self.ticks - ticks0 > DRAIN_TICKS:
                raise RuntimeError("the engine did not drain")
            self.tick()


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def window_numbers(recs: list[Rec], t0: float, t1: float, vocab: int) -> dict:
    """What the clients saw in [t0, t1). ``recs`` is every request known,
    finished or not. A token counts where its stamp falls; a gap counts when
    both its stamps fall in the window; a request counts (attempted, TTFT,
    failed) when it was submitted in the window, whenever it finished."""
    tokens = context_tokens = 0
    gaps: list[float] = []
    ttfts: list[float] = []
    attempted = failed = prompt_tokens = 0
    for r in recs:
        for k, t in enumerate(r.stamps):
            if t0 <= t < t1:
                tokens += 1
                # Token k was made by a step that read prompt + k positions.
                context_tokens += r.prompt_len + k
        gaps.extend(b - a for a, b in zip(r.stamps, r.stamps[1:])
                    if a >= t0 and b < t1)
        if not (t0 <= r.submit_t < t1):
            continue
        attempted += 1
        prompt_tokens += r.prompt_len
        ok = (r.out is not None and len(r.out) == r.want
              and all(0 <= t < vocab for t in r.out))
        if not ok:
            failed += 1
        if r.stamps:
            ttfts.append(r.stamps[0] - r.submit_t)
    return {"tokens": tokens, "gaps": gaps, "ttfts": ttfts,
            "attempted": attempted, "failed": failed,
            "prompt_tokens": prompt_tokens, "seconds": t1 - t0,
            "context_tokens": context_tokens}


def end_to_end_values(win: dict, setup_s: float) -> dict:
    """The end-to-end quantities, by the names BENCHMARK.json may use. A
    metric is reported only where the window has samples for it."""
    out = {"setup_s": setup_s, "out_tok_s": win["tokens"] / win["seconds"]}
    if win["ttfts"]:
        out["ttft_ms_p50"] = 1e3 * percentile(win["ttfts"], 50)
        out["ttft_ms_p90"] = 1e3 * percentile(win["ttfts"], 90)
        out["ttft_ms_p95"] = 1e3 * percentile(win["ttfts"], 95)
    if win["gaps"]:
        out["itl_ms_p50"] = 1e3 * percentile(win["gaps"], 50)
        out["itl_ms_p95"] = 1e3 * percentile(win["gaps"], 95)
        out["itl_ms_p99"] = 1e3 * percentile(win["gaps"], 99)
    out["req_s"] = win["attempted"] / win["seconds"]
    return out


# -- counters and spans -------------------------------------------------------------


def delta(after, before):
    """after - before over nested dicts of numbers (other leaves: after's)."""
    if isinstance(after, dict):
        before = before if isinstance(before, dict) else {}
        return {k: delta(v, before.get(k)) for k, v in after.items()}
    if isinstance(after, (int, float)) and not isinstance(after, bool):
        return after - (before if isinstance(before, (int, float)) else 0)
    return after


def span_totals() -> dict:
    from oncilla_tpu.utils.debug import GLOBAL_TRACER

    return {op: {"count": v["count"], "total_s": v["hist"]["sum_s"]}
            for op, v in GLOBAL_TRACER.snapshot().items()}


# -- correctness --------------------------------------------------------------------


def check_reference(loop: Loop, reference, params, conf: dict,
                    problems: list) -> dict:
    """After the window: the schedule's next requests through the same
    engine with its logits kept, against the family's plain float32 forward,
    teacher-forced on the engine's own tokens. Returns the numbers;
    ``compared_numbers`` holds them to the configuration's ``tolerance``."""
    engine = loop.engine
    engine.keep_logits = True
    loop.clients, loop.accepting = 0, False
    reqs = []
    for _ in range(CHECK_REQUESTS):
        req = loop.nth(loop.next_index)
        reqs.append(req)
        loop._submit(req, loop.clock())
    first = len(loop.done)
    while loop.engine.queue or loop.engine.active:
        loop.tick()
    engine.keep_logits = False
    recs = sorted(loop.done[first:], key=lambda r: r.index)
    dmax, agree, total, absmax = 0.0, 0, 0, 0.0
    for req, rec in zip(reqs, recs):
        out = rec.result.out_tokens
        eng = np.stack(rec.result.out_logits)
        if len(out) != rec.want:
            problems.append(f"check request {rec.index}: {len(out)} tokens "
                            f"of {rec.want}")
            continue
        if not (eng.argmax(-1) == np.asarray(out)).all():
            problems.append(f"check request {rec.index}: a token is not the "
                            "arg-max of its own logits")
        seq = np.asarray([req["tokens"] + out[:-1]], np.int32)
        rows = np.arange(len(req["tokens"]) - 1, seq.shape[1])
        ref = reference.logits_at(params, seq, rows, conf)[0]
        if not (np.isfinite(ref).all() and np.isfinite(eng).all()):
            problems.append(f"check request {rec.index}: non-finite logits")
            continue
        dmax = max(dmax, float(np.abs(ref - eng).max()))
        absmax = max(absmax, float(np.abs(ref).max()))
        agree += int((ref.argmax(-1) == np.asarray(out)).sum())
        total += len(out)
    share = agree / total if total else 0.0
    return {"max_abs_dlogit": dmax, "argmax_share": share,
            "tokens_compared": total, "ref_logit_absmax": absmax}


def check_served(loop: Loop, t0: float, t1: float, seed: int, reference,
                 params, conf: dict, problems: list) -> dict:
    """What the timed path itself produced: a sample, drawn from the seed, of
    the requests that finished inside the window, the longest of them in it.
    The reference runs once over each prompt with its served tokens; the
    number compared is the widest gap by which a served token's reference
    logit lies below the reference's best at its position (greedy decoding:
    0 wherever the program and the reference agree on the arg-max), held
    under ``tolerance_served`` by ``compared_numbers``."""
    done = [r for r in loop.done if r.out and t0 <= r.stamps[-1] < t1]
    if not done:
        return {"served_logit_gap": 0.0, "served_tokens": 0,
                "served_requests": 0}
    longest = max(done, key=lambda r: (r.prompt_len + len(r.out), -r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    sample = [longest] + [rest[i] for i in order[:SERVED_REQUESTS - 1]]
    gap, tokens = 0.0, 0
    for rec in sample:
        prompt = loop.nth(rec.index)["tokens"]
        seq = np.asarray([prompt + rec.out[:-1]], np.int32)
        rows = np.arange(len(prompt) - 1, seq.shape[1])
        ref = reference.logits_at(params, seq, rows, conf)[0]
        if not np.isfinite(ref).all():
            problems.append(f"request {rec.index}: non-finite reference "
                            "logits over its served tokens")
            continue
        served = ref[np.arange(len(rec.out)), np.asarray(rec.out)]
        gap = max(gap, float((ref.max(-1) - served).max()))
        tokens += len(rec.out)
    return {"served_logit_gap": gap, "served_tokens": tokens,
            "served_requests": len(sample)}


def check_guarantees(stats_end: dict, problems: list) -> None:
    """The configuration's guarantees that are no number of the window."""
    if stats_end["degraded"].get("capacity_free", 0):
        problems.append("a tier with free capacity refused a page: "
                        f"{stats_end['degraded']}")
    if stats_end.get("cold_sim"):
        problems.append("COLD was simulated, not on the daemons")


def compared_numbers(cell: Cell, check: dict, stats_win: dict, win: dict,
                     problems: list) -> dict:
    """Every number ``correct`` holds to a limit, beside that limit: the
    reference's (``tolerance``, ``tolerance_served``), the window's requests
    and the mix's ``expect``. ``want`` says on which side of the limit the
    value holds; one that does not is checked into ``problems``. This is
    what a record of a run that was not correct has to show."""
    def entry(value, want, limit):
        return {"value": value, "want": want, "limit": limit}

    tol = cell.config["tolerance"]
    out = {
        "max_abs_dlogit": entry(check["max_abs_dlogit"], "<=",
                                tol["max_abs_dlogit"]),
        "argmax_share": entry(check["argmax_share"], ">=",
                              tol["argmax_share"]),
        "tokens_compared": entry(check["tokens_compared"], ">=", 1),
        "served_logit_gap": entry(
            check["served_logit_gap"], "<=",
            cell.config["tolerance_served"]["max_logit_gap"]),
        "served_tokens": entry(check["served_tokens"], ">=", 1),
        "requests_failed": entry(win["failed"], "<=", 0),
        "requests_attempted": entry(win["attempted"], ">=", 1),
    }
    exp = cell.traffic.get("expect", {})
    for hop in exp.get("window_hops_nonzero", []):
        out[f"hops.{hop}"] = entry(stats_win["moves"]["hops"].get(hop, 0),
                                   ">=", 1)
    if "window_promotes_max" in exp:
        out["window_promotes"] = entry(stats_win["moves"]["promote"], "<=",
                                       exp["window_promotes_max"])
    if "prefix_reused_share_min" in exp and win["prompt_tokens"]:
        out["prefix_reused_share"] = entry(
            win["reused_tokens"] / win["prompt_tokens"], ">=",
            exp["prefix_reused_share_min"])
    for name, c in out.items():
        holds = (c["value"] <= c["limit"] if c["want"] == "<="
                 else c["value"] >= c["limit"])
        if not holds:
            problems.append(f"{name} {c['value']:.6g}, has to be "
                            f"{c['want']} {c['limit']}")
    return out


# -- one run ------------------------------------------------------------------------


def peak_of(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error, never a
    default."""
    peaks = _read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(peaks)})")
    return peaks[device_kind]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, so far in this process."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_line(devices, peak: int, trace: dict | None,
                window_s: float | None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = window_s
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, platform: str = "tpu",
             keep_trace: str | None = None,
             trace_seconds: float = TRACE_SECONDS) -> dict:
    """Run one cell once; returns the last line as a dict. ``platform`` is
    ``tpu`` for every measurement; the CPU tests pass ``cpu`` with a tiny
    configuration."""
    cell = load_cell(workload)
    family = load_family(cell.config)
    import jax

    if jax.default_backend() != platform:
        raise Refused(f"backend is {jax.default_backend()!r}, need "
                      f"{platform!r}: no measurement is taken off the chip")
    devices = jax.devices()
    if len(devices) < cell.chips:
        raise Refused(f"{workload} needs {cell.chips} chips, JAX sees "
                      f"{len(devices)}")
    devices = devices[:cell.chips]

    from oncilla_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    # The engine's small eager programs fall under JAX's one-second
    # threshold and would be rebuilt in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter()
    phases: dict = {}
    mark = [time.perf_counter()]

    def phase(name: str) -> None:
        now = time.perf_counter()
        phases[name] = round(now - mark[0], 2)
        mark[0] = now

    phases["import"] = round(mark[0] - t_start, 2)
    cfg, params = seeded_weights(family, cell.config, seed)
    vocab = int(cell.config["vocab_size"])
    phase("weights")

    gen = load_plugin("generators", cell.traffic["generator"])
    sched = gen.schedule(seed, cell.traffic["params"], vocab)
    problems: list[str] = []
    trace_dir = os.path.join(ROOT, ".bench_trace", workload)
    reduced = None
    traced_s = None
    with serving_stack(cfg, params, cell.traffic["engine"], workload,
                       problems) as (engine, page_bytes):
        phase("build")
        warm = cell.traffic.get("warm", {})
        if warm.get("warmer"):
            load_plugin("warmers", warm["warmer"]).warm(
                engine, cfg, params, warm)
        phase("warm_programs")
        loop = Loop(engine, sched)
        # The ramp seats 1, 2, 4... sessions at a time, so that the small
        # eager programs of every padded batch size exist before the window;
        # a full house alone would skip the sizes below it.
        full, target = loop.clients, 0
        for clients, requests in warm.get("ramp", []) if full else []:
            loop.clients = min(int(clients), full)
            target += int(requests)
            loop.run_until(lambda: len(loop.done) >= target)
        loop.clients = full
        want = max(int(warm.get("requests", 0)), target)
        loop.run_until(lambda: len(loop.done) >= want)
        phase("warm_requests")
        log(f"{workload}: set-up phases {phases}, compile {meter.lap()}, "
            f"cache {cache_dir}")

        # -- the window opens, the loop in flight --
        stats0 = engine.metrics_meta()
        spans0 = span_totals()
        compiles0 = meter.executables
        ticks0 = loop.ticks
        t0 = loop.clock()
        setup_s = t0 - t_start
        t1 = t0 + float(seconds)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            on = min(TRACE_AFTER_S, 0.25 * seconds)
            off = on + min(trace_seconds, 0.5 * seconds)
            loop.run_until(lambda: loop.clock() >= t0 + on)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            p0 = loop.clock()
            loop.run_until(lambda: loop.clock() >= t0 + off)
            traced_s = loop.clock() - p0
            jax.profiler.stop_trace()
        loop.run_until(lambda: loop.clock() >= t1)
        t1 = loop.clock()
        stats1 = engine.metrics_meta()
        spans1 = span_totals()
        window_compiles = meter.executables - compiles0
        window_ticks = loop.ticks - ticks0
        loop.drain()

        recs = loop.done + list(loop.inflight.values())
        win = window_numbers(recs, t0, t1, vocab)
        win["reused_tokens"] = sum(
            r.result.prefix_tokens_reused for r in loop.done
            if t0 <= r.submit_t < t1)
        win["compiles"] = window_compiles
        win["ticks"] = window_ticks
        stats_win = delta(stats1, stats0)
        # The program's peak: a process's peak never falls again, so it is
        # read before the reference puts its float32 layers on the chip.
        peak = memory_peak(devices)
        check = check_served(loop, t0, t1, seed, family.reference, params,
                             cell.config, problems)
        check.update(check_reference(loop, family.reference, params,
                                     cell.config, problems))
        check_guarantees(engine.metrics_meta(), problems)
        compared = compared_numbers(cell, check, stats_win, win, problems)
    # serving_stack's exit checked the drain guarantees into `problems`.

    values = end_to_end_values(win, setup_s)
    if trace:
        trace_reduce = load_plugin("", "trace_reduce")
        xplane = trace_reduce.find_xplane(trace_dir)
        reduced = trace_reduce.reduce(xplane, chips=cell.chips)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace,
                                             f"{workload}.xplane.pb"))
            with open(os.path.join(keep_trace, f"{workload}.reduced.json"),
                      "w") as f:
                json.dump(reduced, f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)
        info = {
            "name": cell.name, "config": cell.config,
            "traffic": cell.traffic, "page_bytes": page_bytes,
            "window": win, "traced_s": traced_s,
            "peak": peak_of(devices[0].device_kind),
            "lib": {"trace_reduce": trace_reduce, "family": family.adapter,
                    "bytes_model": family.bytes_model,
                    "bytes_shared": load_plugin("", "bytes_model")},
        }
        spans_win = delta(spans1, spans0)
        metrics = {}
        for m in cell.per_layer:
            value = load_plugin("layer_metrics", m["name"]).read(
                stats_win, spans_win, reduced, info)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}

    log(f"{workload}: window {win['seconds']:.2f} s, {win['ticks']} ticks, "
        f"{win['tokens']} tokens, {win['attempted']} requests submitted, "
        f"{window_compiles} executables built inside it; "
        f"hops {stats_win['moves']['hops']}; stalls {stats_win['stalls']} "
        f"({stats_win['stall_s']:.3f} s); tiers peak "
        f"{stats1['tier_pages_peak']}; values "
        f"{ {k: round(v, 3) for k, v in values.items()} }; reference {check}; "
        f"total compile {meter.lap()}")
    for p in problems:
        log(f"{workload}: NOT CORRECT: {p}")
    for name, c in compared.items():
        log(f"{workload}: compared {name} {c['value']:.6g} "
            f"(holds {c['want']} {c['limit']})")
    line = {
        "correct": not problems,
        "attempted": win["attempted"],
        "failed": win["failed"],
        "metrics": metrics,
        "device": device_line(devices, peak, reduced, traced_s),
    }
    if reduced is not None:
        line["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    line["compared"] = compared     # last: a record keeps a line's end
    return line
