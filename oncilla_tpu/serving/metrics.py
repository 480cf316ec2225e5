"""Serving-side metrics: counters + the co-located publication registry.

Stdlib-only BY CONTRACT (the same rule as ``obs/``): the runtime daemon
imports this module from ``_on_status`` to pick up a co-located engine's
stats, and that import must never pull jax or the model stack into a
daemon process that serves no model at all.

The serving engine is an *application* (a client of the runtime), so its
metrics cannot ride a daemon's own counters the way qos/elastic state
does. Instead every live :class:`ServingStats` registers itself here;
a daemon **in the same process** (the TPU-VM deployment shape, and every
``local_cluster`` harness) folds :func:`colocated` into its STATUS /
STATUS_PROM tails, which is how the obs cluster table and the
``ocm_serving_*`` Prometheus families light up with zero new MsgTypes —
the PR-9 discipline (observability stays in-band and filesystem/process
-side, never a new wire surface).
"""

from __future__ import annotations

import bisect
import threading

_lock = threading.Lock()
_published: dict[str, "ServingStats"] = {}

#: The phases a scheduler tick's wall time is put down to, in the order of
#: the engine's account (``ServingEngine._acct``, whose one further entry is
#: the time outside the engine, between two ticks).
GAP_PHASES = ("chunk", "build", "device", "scatter", "finish", "sched")

#: Upper bounds, in seconds, of the buckets a token gap and a first-token
#: wait are filed in: geometric, a quarter of an octave apart, 1 ms to
#: 131 s; a last bucket (``inf``) takes what lies beyond.
GAP_BUCKETS = tuple(1e-3 * 2.0 ** (k / 4) for k in range(69))

_ITL_FIELDS = ("count", "sum_s", "ticks", "outside_s") + tuple(
    f"{phase}_s" for phase in GAP_PHASES)
_TTFT_TAIL_FIELDS = ("count", "sum_s", "ticks", "unseated_ticks",
                     "own_chunk_s", "queue_s")


def _file(table: dict, secs: float, row: tuple, count: int = 1) -> None:
    """Add ``count`` entries of ``row`` each into the bucket of ``table``
    (bucket index -> sums, the entries' number first) that ``secs`` puts
    them in."""
    i = bisect.bisect_left(GAP_BUCKETS, secs)
    into = table.get(i)
    if into is None:
        into = table[i] = [0] * (len(row) + 1)
    into[0] += count
    for k, v in enumerate(row, 1):
        into[k] += count * v


def _filed(table: dict, fields: tuple) -> dict:
    """``{upper bound: {field: sum}}`` over the buckets that hold
    something, by rising bound; ``inf`` is what lies beyond the last."""
    return {GAP_BUCKETS[i] if i < len(GAP_BUCKETS) else float("inf"):
            dict(zip(fields, table[i])) for i in sorted(table)}


class ServingStats:
    """Thread-safe counter block for one serving engine.

    All mutation goes through the ``note_*`` methods; :meth:`snapshot`
    returns the plain-dict meta that STATUS tails, ``obs/prom.py`` and
    the cluster table render. Byte figures are *live* occupancy (gauges);
    token/stall/move figures are lifetime counters.
    """

    def __init__(self, engine: str = "engine") -> None:
        self.engine = engine
        self._mu = threading.Lock()
        self.prefill_tokens = 0
        self.decode_tokens = 0
        # Page-residency lookups at schedule time: hit = the page was
        # already decode-resident (hot tier), miss = a fetch was needed.
        self.lookups = 0
        self.hits = 0
        self.promotes = 0
        self.demotes = 0
        # The same moves by hop ("hbm>host", "remote>hbm", ...).
        self.hops: dict[str, int] = {}
        # Allocations / moves a tier's arena refused: ``capacity_free``
        # = refused while the tier held fewer pages than its configured
        # capacity (the arena is not doing what the capacity promised);
        # ``pressure`` = refused at or past capacity (legitimate).
        self.degraded = {"capacity_free": 0, "pressure": 0}
        self.cow_copies = 0
        # Prefix sharing.
        self.prefix_hits = 0
        self.prefix_shared_bytes = 0
        self.prefix_extents = 0
        # A family with a recurrent carry keeps a snapshot of it with
        # every extent: snapshots taken and stored, adoptions (a session
        # taking one extent or a chain of them at a boundary), adoptions
        # that set the session's carry from the last extent's snapshot,
        # and the bytes of the snapshots live extents hold (a gauge).
        self.prefix_carry_snapshots = 0
        self.prefix_adoptions = 0
        self.prefix_carry_restores = 0
        self.prefix_carry_bytes = 0
        # Prefetch / stall.
        self.prefetch_issued = 0
        self.prefetch_completed = 0
        self.stalls = 0
        self.stall_s = 0.0
        # Live per-tier occupancy (set absolutely by the page store).
        self.tier_bytes: dict[str, int] = {}
        self.tier_pages: dict[str, int] = {}
        self.tier_pages_peak: dict[str, int] = {}
        # Cold-tier (remote) data-plane traffic.
        self.remote_bytes_in = 0
        self.remote_bytes_out = 0
        # True-batched decode: per-tick fused-step accounting. size_hist
        # and step_s_hist are cumulative prom-style bucket counts
        # (bucket upper bound -> observations <= bound) so obs/prom.py
        # can render real histograms from a stdlib-only snapshot.
        self.batch_steps = 0
        self.batch_size_sum = 0
        self.batch_size_last = 0
        self.batch_size_max = 0
        self.batch_size_hist = {b: 0 for b in self.BATCH_BUCKETS}
        self.step_s_sum = 0.0
        self.step_s_hist = {b: 0 for b in self.STEP_BUCKETS}
        # Page programs (chunks) and the whole prompt pages they took.
        self.prefill_chunks = 0
        self.prefill_pages = 0
        # The fused step's page pool, kept on the device between ticks:
        # rows a tick found in their slot, rows written (in place, or
        # into a new pool), and pools made (the first; the row bucket
        # changed).
        self.pool_rows_reused = 0
        self.pool_rows_written = 0
        self.pool_rebuilds = 0
        # The dispatches the pool took: group writes (several new rows
        # each), gathers (a crossing of the row bucket: the new pool made
        # from the old one) and the rows the gathers carried over.
        self.pool_group_writes = 0
        self.pool_gathers = 0
        self.pool_rows_carried = 0
        # The seated sessions' tails, kept in one stack on the device
        # between ticks: seats of a fused step whose tail the stack
        # already held (an empty tail is held by any seat), and seats
        # written, moved or placed in a new stack before the step.
        self.tails_seats_kept = 0
        self.tails_seats_written = 0
        # A page's decode-ready arrays, kept once a (page, version) for
        # every session whose context holds the page: pages a session took
        # that a live session already held (it lists that holder's entry,
        # arrays and all: no work), and arrays built from the store's bytes
        # (a residency pass found none at the page's version).
        self.arrays_pages_shared = 0
        self.arrays_pages_rebuilt = 0
        # The same for the carry stack of a family that keeps a recurrent
        # state a session (ServingEngine._seat_carries).
        self.carry_seats_kept = 0
        self.carry_seats_written = 0
        # What a step's experts cost (a family with routed experts; the
        # programs hand the counts back): distinct (layer, expert) pairs
        # that received a real token, over fused steps and over prefill
        # pages, the (token, layer, expert) assignments the fused steps
        # routed, and the pages counted. Fused steps are batch_steps.
        self.moe_step_expert_rows = 0
        self.moe_step_assignments = 0
        self.moe_page_expert_rows = 0
        self.moe_page_count = 0
        # A family whose page comes in kinds, some with a window: pages of
        # such a kind shipped, and dropped once they had left the window
        # of every later query (freed, never demoted).
        self.window_pages_shipped = 0
        self.window_pages_dropped = 0
        # Pages the store freed, the free_pages calls that took (pages
        # freed together are one call) and the device programs their HOT
        # extents' scrubs were.
        self.frees_pages = 0
        self.frees_calls = 0
        self.frees_scrub_dispatches = 0
        # Pages the store placed (sited in a tier: a new page, a
        # copy-on-write clone) and the walks it made over every live page
        # (a victim sought: a tier at its capacity or past its high mark).
        self.places_pages = 0
        self.places_walks = 0
        # Pages the store wrote into HOT a group at a time (alloc_pages'
        # group path) and the device programs those writes took; absent
        # from the snapshot until a group has been written.
        self.hot_writes_pages = 0
        self.hot_writes_dispatches = 0
        # (layer, position) pairs the seated sessions' live pages held,
        # summed over the fused steps, and what they would have held had
        # every cached layer kept every position (equal unless a kind
        # drops pages).
        self.kv_positions_held = 0
        self.kv_positions_whole = 0
        # (layer, position) pairs of the context handed to the page
        # programs, summed over them: every position of a full layer, at
        # most a window's of a layer with one.
        self.kv_page_positions_read = 0
        self.preempts: dict[str, int] = {}
        # Time-to-first-token per session (submit -> first emitted
        # token), same cumulative prom-style bucket shape as the step
        # histogram so the SLO engine can window a quantile over it.
        self.ttft_count = 0
        self.ttft_s_sum = 0.0
        self.ttft_s_hist = {b: 0 for b in self.TTFT_BUCKETS}
        # Where that time went, summed over the same sessions: waiting
        # for max_active, whole prompt pages (adoption, one chunk a
        # tick), the sub-page remainder riding the fused step; and the
        # ticks of that remainder a runnable session spent unseated.
        self.ttft_parts = {"queue_s": 0.0, "chunk_s": 0.0, "tail_s": 0.0,
                           "unseated_ticks": 0}
        # The tails' anatomy (note_gaps): every gap between two tokens of
        # a session, and every wait for a first token, filed by its engine
        # seconds in a bucket of GAP_BUCKETS that adds up what it was made
        # of: a table each (_file), _ITL_FIELDS and _TTFT_TAIL_FIELDS a
        # bucket.
        self._itl: dict[int, list] = {}
        self._ttft_tail: dict[int, list] = {}

    BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)
    STEP_BUCKETS = (0.001, 0.005, 0.025, 0.1, 0.5, 2.5)
    TTFT_BUCKETS = (0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0)

    # -- mutation ---------------------------------------------------------

    def note_tokens(self, n: int, phase: str = "decode") -> None:
        with self._mu:
            if phase == "prefill":
                self.prefill_tokens += n
            else:
                self.decode_tokens += n

    def note_lookup(self, hit: bool) -> None:
        with self._mu:
            self.lookups += 1
            if hit:
                self.hits += 1

    def note_move(self, promote: bool, src: str, dst: str) -> None:
        with self._mu:
            if promote:
                self.promotes += 1
            else:
                self.demotes += 1
            hop = f"{src}>{dst}"
            self.hops[hop] = self.hops.get(hop, 0) + 1

    def note_degrade(self, capacity_free: bool) -> None:
        with self._mu:
            self.degraded[
                "capacity_free" if capacity_free else "pressure"
            ] += 1

    def note_cow(self) -> None:
        with self._mu:
            self.cow_copies += 1

    def note_prefix_hit(self, shared_bytes: int) -> None:
        with self._mu:
            self.prefix_hits += 1
            self.prefix_shared_bytes += shared_bytes

    def note_prefix_release(self, shared_bytes: int) -> None:
        with self._mu:
            self.prefix_shared_bytes -= shared_bytes

    def note_extents(self, delta: int) -> None:
        with self._mu:
            self.prefix_extents += delta

    def note_carry_snapshot(self) -> None:
        with self._mu:
            self.prefix_carry_snapshots += 1

    def note_carry_bytes(self, delta: int) -> None:
        with self._mu:
            self.prefix_carry_bytes += delta

    def note_adoption(self, restored: bool) -> None:
        with self._mu:
            self.prefix_adoptions += 1
            self.prefix_carry_restores += restored

    def note_prefetch(self, completed: bool = False) -> None:
        with self._mu:
            if completed:
                self.prefetch_completed += 1
            else:
                self.prefetch_issued += 1

    def note_stall(self, seconds: float) -> None:
        with self._mu:
            self.stalls += 1
            self.stall_s += seconds

    def note_remote(self, nbytes: int, inbound: bool) -> None:
        with self._mu:
            if inbound:
                self.remote_bytes_in += nbytes
            else:
                self.remote_bytes_out += nbytes

    def note_batch_step(self, size: int, seconds: float) -> None:
        """One fused batched decode tick: ``size`` sessions advanced one
        token in one jit dispatch taking ``seconds``."""
        with self._mu:
            self.batch_steps += 1
            self.batch_size_sum += size
            self.batch_size_last = size
            self.batch_size_max = max(self.batch_size_max, size)
            self.step_s_sum += seconds
            for b in self.BATCH_BUCKETS:
                if size <= b:
                    self.batch_size_hist[b] += 1
            for b in self.STEP_BUCKETS:
                if seconds <= b:
                    self.step_s_hist[b] += 1

    def note_ttft(self, seconds: float, queue_s: float = 0.0,
                  chunk_s: float = 0.0, tail_s: float = 0.0,
                  unseated_ticks: int = 0) -> None:
        """One session's time-to-first-token and its parts
        (``queue_s + chunk_s + tail_s == seconds`` from the engine)."""
        with self._mu:
            self.ttft_count += 1
            self.ttft_s_sum += seconds
            for b in self.TTFT_BUCKETS:
                if seconds <= b:
                    self.ttft_s_hist[b] += 1
            parts = self.ttft_parts
            parts["queue_s"] += queue_s
            parts["chunk_s"] += chunk_s
            parts["tail_s"] += tail_s
            parts["unseated_ticks"] += unseated_ticks

    def note_gaps(self, gaps, firsts=()) -> None:
        """One tick's token gaps, all at once. ``gaps`` is ``(parts, ticks,
        count)`` for each set of ``count`` sessions whose gap is the same
        (they emitted in the same two ticks): ``parts`` the seconds of each
        of GAP_PHASES and then the seconds outside the engine, ``ticks``
        the ticks spanned. A gap is filed by its engine seconds, the sum of
        its six phases. ``firsts`` are the tick's first tokens, ``(engine
        seconds since admission, ticks, unseated_ticks, own_chunk_s,
        queue_s)`` each, filed the same way."""
        n = len(GAP_PHASES)
        with self._mu:
            for parts, ticks, count in gaps:
                secs = sum(parts[:n])
                _file(self._itl, secs,
                      (secs, ticks, parts[n], *parts[:n]), count)
            for first in firsts:
                _file(self._ttft_tail, first[0], first)

    def note_preempt(self, reason: str) -> None:
        """A session lost (or yielded) its batch slot this tick:
        ``slot`` = lost priority-ordered slot contention, ``cold_page``
        = yielded because its pages had not prefetched yet."""
        with self._mu:
            self.preempts[reason] = self.preempts.get(reason, 0) + 1

    def note_pool(self, reused: int = 0, written: int = 0,
                  rebuilt: bool = False, group_writes: int = 0,
                  gathers: int = 0, carried: int = 0) -> None:
        """One tick's page pool: ``reused`` rows of the batch had a row
        already (one carried over a crossing too: nothing was written for
        it), ``written`` rows went to the device; ``rebuilt`` when the pool
        was made anew (the first, or the row bucket changed). The
        dispatches that took: ``group_writes``, and ``gathers`` that
        ``carried`` rows into the new pool, the batch's or not."""
        with self._mu:
            self.pool_rows_reused += reused
            self.pool_rows_written += written
            self.pool_rebuilds += int(rebuilt)
            self.pool_group_writes += group_writes
            self.pool_gathers += gathers
            self.pool_rows_carried += carried

    def note_tails(self, kept: int = 0, written: int = 0) -> None:
        """One fused step's seats: ``kept`` cost nothing, ``written``
        took a dispatch (a joiner's tail written, the last seat moved
        into a hole) or were placed in a new stack."""
        with self._mu:
            self.tails_seats_kept += kept
            self.tails_seats_written += written

    def note_arrays(self, shared: int = 0, rebuilt: int = 0) -> None:
        """A page's decode arrays: ``shared`` pages entered a context that
        a live session already held, ``rebuilt`` arrays were built from the
        store's bytes."""
        with self._mu:
            self.arrays_pages_shared += shared
            self.arrays_pages_rebuilt += rebuilt

    def note_carry(self, kept: int = 0, written: int = 0) -> None:
        """One fused step's seats of the carry stack: ``kept`` carries
        were in their seat already, ``written`` were written in (a
        joiner's) or moved with their seat."""
        with self._mu:
            self.carry_seats_kept += kept
            self.carry_seats_written += written

    def note_moe_step(self, expert_rows: int, assignments: int) -> None:
        """One fused step of a family with experts: ``expert_rows``
        distinct (layer, expert) pairs were read for ``assignments``
        (token, layer, expert) routings of real rows."""
        with self._mu:
            self.moe_step_expert_rows += expert_rows
            self.moe_step_assignments += assignments

    def note_moe_page(self, expert_rows: int, pages: int = 1) -> None:
        """``pages`` prefill pages of a family with experts (one, or as
        many as the engine summed on the device before it looked)."""
        with self._mu:
            self.moe_page_expert_rows += expert_rows
            self.moe_page_count += pages

    def note_window(self, shipped: int = 0, dropped: int = 0) -> None:
        """Pages of a kind with a window: ``shipped`` into the store,
        ``dropped`` from it because they had left the window."""
        with self._mu:
            self.window_pages_shipped += shipped
            self.window_pages_dropped += dropped

    def note_frees(self, pages: int, scrub_dispatches: int) -> None:
        """One ``free_pages`` call of the store: ``pages`` freed, their
        HOT extents scrubbed by ``scrub_dispatches`` device programs."""
        with self._mu:
            self.frees_pages += pages
            self.frees_calls += 1
            self.frees_scrub_dispatches += scrub_dispatches

    def note_place(self) -> None:
        """The store sited one new page in a tier."""
        with self._mu:
            self.places_pages += 1

    def note_hot_write(self, pages: int, dispatches: int) -> None:
        """The store placed ``pages`` new pages in HOT together, their
        bytes written by ``dispatches`` device programs."""
        with self._mu:
            self.hot_writes_pages += pages
            self.hot_writes_dispatches += dispatches

    def note_walk(self) -> None:
        """The store walked every live page once (``_victims``)."""
        with self._mu:
            self.places_walks += 1

    def note_kv(self, held: int, whole: int) -> None:
        """One fused step's context: ``held`` (layer, position) pairs in
        the seated sessions' live pages, ``whole`` had nothing been
        dropped."""
        with self._mu:
            self.kv_positions_held += held
            self.kv_positions_whole += whole

    def note_kv_page(self, read: int) -> None:
        """One page program's context: ``read`` (layer, position) pairs."""
        with self._mu:
            self.kv_page_positions_read += read

    def note_prefill_chunk(self, pages: int = 1) -> None:
        """One page program, over ``pages`` whole pages of a prompt."""
        with self._mu:
            self.prefill_chunks += 1
            self.prefill_pages += pages

    def set_occupancy(self, tier_pages: dict[str, int],
                      tier_bytes: dict[str, int]) -> None:
        with self._mu:
            self.tier_pages = dict(tier_pages)
            self.tier_bytes = dict(tier_bytes)
            for tier, n in tier_pages.items():
                if n > self.tier_pages_peak.get(tier, 0):
                    self.tier_pages_peak[tier] = n

    # -- export -----------------------------------------------------------

    @property
    def hit_ratio(self) -> float:
        with self._mu:
            return self.hits / self.lookups if self.lookups else 0.0

    def snapshot(self) -> dict:
        with self._mu:
            lookups, hits = self.lookups, self.hits
            itl = _filed(self._itl, _ITL_FIELDS)
            snap = {
                "engine": self.engine,
                "tokens": {
                    "prefill": self.prefill_tokens,
                    "decode": self.decode_tokens,
                },
                "lookups": lookups,
                "hits": hits,
                "hit_ratio": round(hits / lookups, 4) if lookups else 0.0,
                "tier_bytes": dict(self.tier_bytes),
                "tier_pages": dict(self.tier_pages),
                "tier_pages_peak": dict(self.tier_pages_peak),
                "prefix": {
                    "hits": self.prefix_hits,
                    "shared_bytes": max(self.prefix_shared_bytes, 0),
                    "extents": self.prefix_extents,
                    "cow": self.cow_copies,
                    "adoptions": self.prefix_adoptions,
                    "carry_snapshots": self.prefix_carry_snapshots,
                    "carry_restores": self.prefix_carry_restores,
                    "carry_bytes": self.prefix_carry_bytes,
                },
                "stalls": self.stalls,
                "stall_s": round(self.stall_s, 6),
                "prefetch": {
                    "issued": self.prefetch_issued,
                    "completed": self.prefetch_completed,
                },
                "moves": {
                    "promote": self.promotes,
                    "demote": self.demotes,
                    "hops": dict(self.hops),
                },
                "degraded": dict(self.degraded),
                "remote_bytes": {
                    "in": self.remote_bytes_in,
                    "out": self.remote_bytes_out,
                },
                "batch": {
                    "steps": self.batch_steps,
                    "size_sum": self.batch_size_sum,
                    "size_last": self.batch_size_last,
                    "size_max": self.batch_size_max,
                    "size_hist": dict(self.batch_size_hist),
                    "step_s": round(self.step_s_sum, 6),
                    "step_s_hist": dict(self.step_s_hist),
                    "prefill_chunks": self.prefill_chunks,
                },
                "prefill": {"pages": self.prefill_pages},
                "pool": {
                    "rows_reused": self.pool_rows_reused,
                    "rows_written": self.pool_rows_written,
                    "rebuilds": self.pool_rebuilds,
                },
                "pool_dispatches": {
                    "group_writes": self.pool_group_writes,
                    "gathers": self.pool_gathers,
                    "rows_carried": self.pool_rows_carried,
                },
                "tails": {
                    "seats_kept": self.tails_seats_kept,
                    "seats_written": self.tails_seats_written,
                },
                "arrays": {
                    "pages_shared": self.arrays_pages_shared,
                    "pages_rebuilt": self.arrays_pages_rebuilt,
                },
                "carry": {
                    "seats_kept": self.carry_seats_kept,
                    "seats_written": self.carry_seats_written,
                },
                "moe": {
                    "step_expert_rows": self.moe_step_expert_rows,
                    "step_assignments": self.moe_step_assignments,
                    "page_expert_rows": self.moe_page_expert_rows,
                    "page_count": self.moe_page_count,
                },
                "window": {
                    "pages_shipped": self.window_pages_shipped,
                    "pages_dropped": self.window_pages_dropped,
                },
                "frees": {
                    "pages": self.frees_pages,
                    "calls": self.frees_calls,
                    "scrub_dispatches": self.frees_scrub_dispatches,
                },
                "places": {
                    "pages": self.places_pages,
                    "walks": self.places_walks,
                },
                "kv": {
                    "positions_held": self.kv_positions_held,
                    "positions_whole": self.kv_positions_whole,
                    "page_positions_read": self.kv_page_positions_read,
                },
                "preempts": dict(self.preempts),
                "ttft": {
                    "count": self.ttft_count,
                    "sum_s": round(self.ttft_s_sum, 6),
                    "hist": dict(self.ttft_s_hist),
                    "parts": {k: round(v, 6)
                              for k, v in self.ttft_parts.items()},
                    "tail_hist": _filed(self._ttft_tail,
                                        _TTFT_TAIL_FIELDS),
                },
                "itl": {
                    "count": sum(b["count"] for b in itl.values()),
                    "sum_s": sum(b["sum_s"] for b in itl.values()),
                    "outside_s": sum(b["outside_s"] for b in itl.values()),
                    "hist": itl,
                },
            }
            if self.hot_writes_pages:
                snap["hot_writes"] = {
                    "pages": self.hot_writes_pages,
                    "dispatches": self.hot_writes_dispatches,
                }
            return snap


# -- co-located publication -------------------------------------------------


def publish(stats: ServingStats) -> None:
    """Register a live engine's stats for same-process daemons to fold
    into their STATUS tails. Idempotent per engine name (latest wins —
    a restarted engine under the same name replaces the stale block)."""
    with _lock:
        _published[stats.engine] = stats


def unpublish(stats: ServingStats) -> None:
    with _lock:
        cur = _published.get(stats.engine)
        if cur is stats:
            del _published[stats.engine]


def colocated() -> dict | None:
    """Snapshot every published engine's meta: the ``serving`` STATUS /
    prom tail, or None when no engine lives in this process."""
    with _lock:
        stats = list(_published.values())
    if not stats:
        return None
    return {"engines": [s.snapshot() for s in stats]}
