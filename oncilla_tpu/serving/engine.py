"""Continuous-batching decode engine over the tiered KV page store.

The compute half of the serving scenario: sessions (one per tenant
request) are admitted as seats free up (the batch composition changes
continuously, it never drains), and every session's KV context lives as
pages in the :class:`~oncilla_tpu.serving.tiers.TieredPageStore`, shared
across tenants through the
:class:`~oncilla_tpu.serving.prefix.PrefixCache`.

There is one scheduler, the tick (:meth:`ServingEngine._tick`):

- **Admission** — queued requests take the places ``max_active`` leaves,
  higher QoS classes first.
- **Prefill with prefix reuse** — at every page boundary a session walks
  the prefix trie; matched extents are acquired (refcounted) and their
  KV is never recomputed. Of the unmatched remainder, each session with a
  whole page of prompt left teacher-forces ONE page a tick through the
  family's page program (chunked prefill), and the sub-page end rides
  the fused step a token a tick. Every completed prompt-only page is
  *published* back into the trie (content-hash dedup) so the next tenant
  hits it. A matched **partial** tail extent is adopted by
  copy-on-write: the shared page stays byte-exact for everyone else,
  the adopter continues into its private clone.
- **One fused step** — up to ``max_batch`` of the remaining sessions are
  seated, by class, and ONE dispatch of the family's step advances each
  by a token. The step's page pool and the seated sessions' tails are
  device state the engine keeps between ticks (:meth:`_batch_pool`,
  :meth:`_seat_batch`).
- **Residency and prefetch** — every tick issues fetches for the
  non-resident pages of all admitted sessions, threaded (default) or as
  AsyncOcm coroutines on the PR-13 mux loop (``OCM_MUX=1``); a session
  whose fetch is still in flight gives its seat up for the tick. A chunk
  promotes its session's pages one by one, a step the whole batch's
  under one watermark sweep. When the prefetch loses the race the wait
  is recorded as page-fault stall time (``prefetch_stall`` journal event
  + the stall counters).
- **Determinism** — greedy decode (temperature 0) over exact page
  round-trips (a page is cast to ``store_dtype``, at least as wide as
  the model dtype, and back): a page's bytes never depend on the tier it
  lives in or on how a chaos schedule reshuffles the remote owners
  mid-decode. On the CPU backend in float32 the emitted token ids are
  then a pure function of (params, prompt), which the chaos gates assert
  byte for byte. On an accelerator the matmul tiling — and with it the
  last bit of a logit — may change with the batch shape, so there the
  check is logit-level agreement with the unpaged forward
  (``chip_smoke.py``, via ``keep_logits``).
"""

from __future__ import annotations

import math
import os
import time
import weakref
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.core.hbm import _pow2_chunks, from_bytes, to_bytes
from oncilla_tpu.models import (
    paged_decode_batch_step_jit,
    paged_decode_page_jit,
)
from oncilla_tpu.models.kv_paging import PagedFamily
from oncilla_tpu.obs import journal as obs_journal
from oncilla_tpu.qos.policy import PRIO_NORMAL
from oncilla_tpu.serving import metrics as serving_metrics
from oncilla_tpu.serving.metrics import GAP_PHASES, ServingStats
from oncilla_tpu.serving.prefix import PrefixCache, SharedExtent
from oncilla_tpu.serving.tiers import Page, Tier, TieredPageStore
from oncilla_tpu.utils.debug import GLOBAL_TRACER, printd


# Where each phase lies in the tick's account (``ServingEngine._acct``):
# the phases of ``metrics.GAP_PHASES``, then the time outside the engine.
_CHUNK, _BUILD, _DEVICE, _SCATTER, _FINISH, _SCHED, _OUTSIDE = range(
    len(GAP_PHASES) + 1)


def _pow2(n: int) -> int:
    """Smallest power of two >= n (shape-bucket policy: padded batch /
    page-table dims snap up so XLA compiles O(log) programs)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# The dense grouped-query family (``models/llama.py``): a page is a K and a
# V of (L, 1, KV, P, Hd). Its programs are looked up in this module's
# namespace at every call, with the arguments they have always had: tools
# that count or perturb them (``benchmark/census.py``, the tests) replace
# the names here.


def _dense_leaf_dims(cfg) -> tuple:
    return (cfg.n_kv_heads, cfg.head_dim)


def _dense_step(params, tokens, meta, n_real, pool, table, tails, cfg):
    logits, tail_k, tail_v = paged_decode_batch_step_jit(
        params, tokens, meta, *pool, table, *tails, cfg)
    return logits, (tail_k, tail_v), None


def _dense_page(params, tokens_page, meta, ctx, tails, cfg):
    logits, tail_k, tail_v = paged_decode_page_jit(
        params, tokens_page, meta, *ctx, *tails, cfg)
    return logits, (tail_k, tail_v), None


DENSE_FAMILY = PagedFamily(
    n_leaves=2, leaf_dims=_dense_leaf_dims, step=_dense_step,
    page=_dense_page,
)


# The tails of the seated sessions live in ONE stack, a leaf of the family's
# page each (L, b_pad, KV, P, Hd), that the engine owns between ticks
# (:meth:`ServingEngine._seat_batch`). A seat changes hands through these
# three programs, all leaves in one dispatch, the seat a traced index: one
# executable a stack shape serves every seat. A family with a carry has a
# second such stack, of its carry's leaves, through the same programs.


@partial(jax.jit, donate_argnums=(0,))
def _seat_write_jit(stack: tuple, tail: tuple, seat: jax.Array) -> tuple:
    """Write one session's tail, (L, 1, KV, P, Hd) a leaf, into ``seat``."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(s, t, seat, axis=1)
                 for s, t in zip(stack, tail))


@partial(jax.jit, donate_argnums=(0,))
def _seat_move_jit(stack: tuple, src: jax.Array, dst: jax.Array) -> tuple:
    """Copy seat ``src`` over seat ``dst`` (the last seat fills a hole)."""
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(
            s, jax.lax.dynamic_slice_in_dim(s, src, 1, axis=1), dst, axis=1)
        for s in stack)


@jax.jit
def _seat_read_jit(stack: tuple, seat: jax.Array) -> tuple:
    """The tail in ``seat``, (L, 1, KV, P, Hd) a leaf, as arrays of its own."""
    return tuple(jax.lax.dynamic_slice_in_dim(s, seat, 1, axis=1)
                 for s in stack)


@partial(jax.jit, static_argnames=("dtype",))
def _pack_pages_jit(kinds: tuple, dtype: str) -> tuple:
    """A full tail on its way into the store, in ONE dispatch whatever the
    family: a kind's leaves stacked, cast to the store's type and flattened
    to bytes (``core.hbm.to_bytes``). Returns a uint8 vector a kind."""
    return tuple(to_bytes(jnp.stack(leaves).astype(jnp.dtype(dtype)))
                 for leaves in kinds)


@partial(jax.jit, static_argnames=("dtype",))
def _pack_chunk_jit(kinds: tuple, dtype: str) -> tuple:
    """A chunk's page tails on their way into the store, in ONE dispatch:
    ``kinds[k][j]`` is page ``j``'s leaves of kind ``k``, each page packed
    as :func:`_pack_pages_jit` packs it. Returns a uint8 ``(pages,
    nbytes)`` array a kind; a chunk's padding pages are packed too, so one
    program serves every count of real pages."""
    return tuple(
        jnp.stack([to_bytes(jnp.stack(leaves).astype(jnp.dtype(dtype)))
                   for leaves in pages])
        for pages in kinds)


# A carry on its way into a prefix extent and back (a family with a carry,
# the prefix cache on): every leaf's bytes one after another, the float32
# they are held in, so a snapshot round-trips bit for bit.


@jax.jit
def _carry_pack_jit(carry: tuple) -> jax.Array:
    """One session's carry, (L, 1, ...) a leaf, as one uint8 vector."""
    return jnp.concatenate([to_bytes(leaf) for leaf in carry])


@partial(jax.jit, static_argnames=("spec",))
def _carry_unpack_jit(data: jax.Array, spec: tuple) -> tuple:
    """The inverse: ``spec`` is the (shape, dtype name) of every leaf. The
    leaves are fresh arrays (the programs donate a carry)."""
    out, at = [], 0
    for shape, dtype in spec:
        n = math.prod(shape) * jnp.dtype(dtype).itemsize
        out.append(from_bytes(data[at:at + n], shape, dtype))
        at += n
    return tuple(out)


# The fused step's page pool, one array of rows (capacity, L, KV, P, Hd) a
# leaf, is brought up to a batch by these two programs, for every family
# alike (:meth:`ServingEngine._kind_pool`).

#: The most pages one dispatch of :func:`_pool_write_jit` writes. A tick's
#: new pages go in power-of-two groups up to this many (as
#: ``core.hbm._pow2_chunks`` cuts a fill), so a pool shape has five write
#: programs and no group is padded. On the chip a dispatch costs the host
#: 0.43-0.47 ms from 4 pages to 16 of the dense family (0.46 for one row),
#: and a row written twice is not free: 0.27 ms of device time in the
#: latent family's pool (PERF.md section 6, PR 37).
_POOL_GROUP = 16


@partial(jax.jit, donate_argnums=(0,))
def _pool_write_jit(pool: tuple, pages: tuple, slots: jax.Array) -> tuple:
    """Write a group of pages, (L, 1, KV, P, Hd) a leaf each, into the rows
    ``slots`` of the pool, in place: the pool is donated and the slots are
    traced, so one program a pool shape and group size serves every row. A
    row is a byte copy of its page (``dynamic_update_slice``), so a pool
    kept up to date this way is bitwise the pool stacked from the same
    pages."""
    for g, page in enumerate(pages):
        at = (slots[g], 0, 0, 0, 0)
        pool = tuple(jax.lax.dynamic_update_slice(rows, leaf[None, :, 0], at)
                     for rows, leaf in zip(pool, page))
    return pool


@jax.jit
def _pool_gather_jit(pool: tuple, idx: jax.Array) -> tuple:
    """A pool of ``len(idx)`` rows made from another's: row ``i`` is the old
    pool's row ``idx[i]``, a byte copy, every leaf in the one dispatch."""
    return tuple(rows.at[idx].get(mode="promise_in_bounds") for rows in pool)


def _zero_carry(family: PagedFamily, cfg, batch: int) -> tuple | None:
    """Fresh zeros of a family's carry for ``batch`` seats (the programs
    donate them), or None for a family that keeps none."""
    if family.carry_leaves is None:
        return None
    return tuple(jnp.zeros(shape, dt)
                 for shape, dt in family.carry_leaves(cfg, batch))


def family_of(cfg) -> PagedFamily:
    """The model family of a config: its own ``paged_family``, or the dense
    grouped-query one."""
    return getattr(cfg, "paged_family", None) or DENSE_FAMILY


@dataclass
class Request:
    """One tenant's generation request (greedy decode: deterministic).
    ``priority`` is a PR-6 QoS class (PRIO_LOW/NORMAL/HIGH): the
    scheduler admits and seats higher classes first under contention."""

    tenant: str
    tokens: list[int]
    max_new_tokens: int = 16
    priority: int = PRIO_NORMAL


@dataclass
class SessionResult:
    tenant: str
    prompt_len: int
    out_tokens: list[int]
    stall_s: float
    prefix_tokens_reused: int
    #: With ``keep_logits``: the float32 logits row each emitted token
    #: was picked from, for logit-level checks against a reference.
    out_logits: list[np.ndarray] | None = None
    #: Where the time to the first token went (``queue_s``, ``chunk_s``,
    #: ``tail_s``: they sum to the TTFT :meth:`ServingStats.note_ttft`
    #: recorded; ``unseated_ticks``). None when no first token was timed.
    ttft_parts: dict | None = None


class Prefetcher:
    """Fetch page bytes ahead of schedule into reusable registered
    buffers. ``workers == 0`` disables prefetch entirely (every miss is
    a synchronous page fault — the chaos leg runs this way so the
    logical-op chaos clock stays deterministic). With a mux-backed cold
    client (``OCM_MUX=1``) cold-tier fetches ride
    :class:`~oncilla_tpu.runtime.mux.AsyncOcm` coroutines on the shared
    event loop — zero extra threads, tagged pipelining on the one
    connection per peer."""

    def __init__(self, store: TieredPageStore, workers: int = 2,
                 stats: ServingStats | None = None):
        self.store = store
        self.stats = stats or store.stats
        self.workers = workers
        self._pool = None
        self._aocm = None
        self._mux_rt = None
        self._bufs: list[np.ndarray] = []
        self._futures: dict[int, object] = {}
        if workers <= 0:
            return
        client = store.cold_backend
        rt = getattr(client, "_mux", None) if client is not None else None
        if rt is not None:
            try:
                self._open_async(client, rt)
            except Exception as e:  # noqa: BLE001 — degrade to threads
                printd("serving: AsyncOcm prefetch unavailable (%s); "
                       "using threads", e)
        if self._aocm is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="ocm-prefetch"
            )

    def _open_async(self, client, rt) -> None:
        from oncilla_tpu.runtime.mux import AsyncOcm

        self._aocm = rt.run(AsyncOcm.open(
            client.entries, client.rank, config=client.config,
            channels=rt.channels, heartbeat=False,
        ))
        self._mux_rt = rt

    @property
    def mode(self) -> str:
        if self._aocm is not None:
            return "async"
        return "thread" if self._pool is not None else "off"

    def _buf(self) -> np.ndarray:
        return (self._bufs.pop() if self._bufs
                else np.empty(self.store.page_bytes, dtype=np.uint8))

    def submit(self, page: Page) -> None:
        """Schedule a fetch of ``page`` (idempotent per page)."""
        if self.mode == "off" or page.page_id in self._futures:
            return
        if self.mode == "async" and page.tier != Tier.COLD:
            return  # warm reads are local memcpys; not worth a coroutine
        buf = self._buf()
        version = page.version
        self.stats.note_prefetch()
        if self._aocm is not None:
            nbytes = page.nbytes

            async def go():
                await self._aocm.get(page.handle, nbytes, 0,
                                     out=buf[:nbytes])
                self.stats.note_remote(nbytes, inbound=True)
                return (buf, version, True)

            self._futures[page.page_id] = self._mux_rt.submit(go())
        else:
            def fetch():
                ver, ok = self.store.fetch_bytes(page, buf)
                return (buf, ver, ok)

            self._futures[page.page_id] = self._pool.submit(fetch)

    def take(self, page_id: int):
        """The pending future for ``page_id`` (consumed), or None."""
        return self._futures.pop(page_id, None)

    def pending(self, page_id: int) -> bool:
        """True while a submitted fetch for ``page_id`` has not landed —
        the scheduler's yield-on-cold probe (a session whose
        fetches are still in flight gives up its slot instead of making
        the whole batch wait)."""
        fut = self._futures.get(page_id)
        if fut is None:
            return False
        done = getattr(fut, "done", None)
        return not done() if done is not None else False

    def recycle(self, buf: np.ndarray) -> None:
        if len(self._bufs) < max(self.workers, 2):
            self._bufs.append(buf)

    def close(self) -> None:
        for fut in self._futures.values():
            try:
                fut.cancel()
            except Exception as e:  # noqa: BLE001 — best-effort teardown
                printd("serving: prefetch cancel failed: %s", e)
        self._futures.clear()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
        if self._aocm is not None:
            try:
                self._mux_rt.run(self._aocm.aclose(detach=True))
            except Exception as e:  # noqa: BLE001 — the runtime may
                # already be shut down by the owning client's close
                printd("serving: AsyncOcm close failed: %s", e)
            self._aocm = None


@dataclass(eq=False)
class _Entry:
    """One page of a context, with its decode-ready arrays. A private page
    has one entry, in its session's list; a shared extent's page has one
    entry too, listed by every live session whose context holds the page
    (:meth:`ServingEngine._entry_of`), so the arrays are built once a
    ``(page, version)`` and every holder reads the same ones. Sharing them
    is safe: device arrays are immutable and no program donates a page's
    arrays (``_context`` concatenates, the pool's group write copies)."""

    page: Page
    extent: SharedExtent | None = None
    #: True while this page's KV is still being produced in the tail
    #: (a CoW-adopted partial): storage-side only, excluded from the
    #: attention context.
    pending_fill: bool = False
    arrays: tuple | None = None   # the page's leaves, decode-ready, cfg dtype
    version: int = -1             # page.version the arrays were built at
    kind: int = 0                 # which of the family's page kinds it is


class _Session:
    def __init__(self, req: Request, leaf_shapes: tuple, dtype,
                 carry: tuple | None = None, n_kinds: int = 1):
        self.req = req
        self.prompt = [int(t) for t in req.tokens]
        #: The context's pages in the order they were taken or shipped,
        #: every kind's in one list (``_Entry.kind``); a kind's pages are
        #: in context order among themselves.
        self.entries: list[_Entry] = []
        #: Pages of each kind dropped from the front of the context: the
        #: kind's first page left starts at ``dropped * page_tokens``.
        self.dropped = [0] * n_kinds
        self.shared_refs: list[SharedExtent] = []
        self.out: list[int] = []
        self.logits: list[np.ndarray] = []
        self.pos = 0
        self.prompt_consumed = 0
        self.tail_len = 0
        self.page_toks: list[int] = []  # token ids whose KV fills the tail
        self.chain_parent: SharedExtent | None = None
        self.chain_valid = True
        self.prefix_tokens_reused = 0
        self.stall_s = 0.0
        self.done = False
        self.priority = int(getattr(req, "priority", PRIO_NORMAL))
        self.submit_t = float(getattr(req, "_submit_t", 0.0) or 0.0)
        self.ttft_noted = False
        # Request anatomy, on submit_t's clock: admission, and the first
        # moment no whole page of prompt remains (the sub-page remainder
        # then rides the fused step one token a tick).
        self.admit_t = 0.0
        self.pages_done_t: float | None = None
        self.unseated_ticks = 0
        self.ttft_parts: dict | None = None
        # The tails' anatomy: the engine's account and its count of ticks
        # as they stood when the tick that made this session's last token
        # closed its books (one tuple for all that tick's sessions; None
        # before the first token), the same at admission (the account's
        # engine seconds, summed), and the ``serve_prefill_chunk`` spans
        # that were this session's own.
        self.gap_mark: tuple | None = None
        self.admit_engine_s = 0.0
        self.admit_tick = 0
        self.own_chunk_s = 0.0
        self._leaf_shapes = leaf_shapes
        self._tail_dt = jnp.dtype(dtype)
        #: The seat of the engine's tail stack that holds this session's
        #: tail, or None while the session holds it itself.
        self.seat: int | None = None
        #: The page being filled, one array a leaf of the family's page;
        #: None while a seat holds it.
        self.tails: tuple | None = None
        #: A family with a carry: the session's recurrent state, one array
        #: a leaf, zeros before its first token; None while a seat holds
        #: it.
        self.carry = carry
        #: The snapshot of the carry taken before the step that computes
        #: the prompt's last token, until ``_publish_partial`` hands it to
        #: the partial tail's extent.
        self.boundary: Page | None = None
        self.reset_tail()

    def reset_tail(self) -> None:
        # FRESH zeros every page, for two reasons: published partial
        # pages must be deterministic byte-for-byte beyond their fill,
        # and the decode step donates the tail buffers — a cached zeros
        # array would be consumed by the first donation and poison every
        # later page.
        self.tails = tuple(jnp.zeros(shape, self._tail_dt)
                           for shape in self._leaf_shapes)
        self.tail_len = 0
        self.page_toks = []


class ServingEngine:
    """Tick-driven continuous batching over one page store: a tick
    admits by class, prefills one page a session that has one left, and
    advances every seated session a token in one fused step."""

    def __init__(
        self,
        params: dict,
        cfg,
        store: TieredPageStore,
        prefix: PrefixCache | None = None,
        page_tokens: int = 16,
        max_active: int = 4,
        prefetch_workers: int | None = None,
        store_dtype: str = "float32",
        name: str = "engine",
        share_partials: bool = True,
        step_budget_ms: int | None = None,
        batched: bool = True,
        max_batch: int | None = None,
        keep_logits: bool = False,
    ):
        self.params = params
        self.cfg = cfg
        self.store = store
        self.prefix = prefix
        self.page_tokens = int(page_tokens)
        self.max_active = int(max_active)
        self.store_dtype = store_dtype
        self.share_partials = share_partials
        self.stats = store.stats
        self.stats.engine = name
        if prefetch_workers is None:
            prefetch_workers = int(os.environ.get("OCM_SERVE_PREFETCH", "2"))
        self.prefetcher = Prefetcher(store, prefetch_workers, self.stats)
        # Per-tick time budget (resilience/timebudget.py,
        # OCM_STEP_BUDGET_MS): bounds how long one tick may sit on a
        # straggling PREFETCH — past the budget the wait is abandoned and
        # the page faults synchronously with the wait accounted as stall,
        # so one slow cold fetch degrades to stall-accounting instead of
        # wedging the whole batch. 0/None = no budget: a wait is bounded
        # by _obtain's 120 s alone.
        if step_budget_ms is None:
            step_budget_ms = int(
                os.environ.get("OCM_STEP_BUDGET_MS", "0") or 0
            )
        self.step_budget_ms = max(0, int(step_budget_ms))
        self._step_budget = None
        if not batched:
            raise ValueError(
                "batched=False: the session-interleaved loop is gone "
                "(PR 31); batch-of-one is the fused step at max_batch=1")
        if max_batch is None:
            max_batch = int(os.environ.get("OCM_SERVING_MAX_BATCH", "8"))
        self.max_batch = max(1, int(max_batch))
        self.keep_logits = bool(keep_logits)
        # The model family of cfg: the leaves of a page and the programs
        # dispatched over them.
        self.family = family_of(cfg)
        self._has_carry = self.family.carry_leaves is not None
        # One session's carry as a prefix extent keeps it: the (shape,
        # dtype) of every leaf and the bytes of them all.
        leaves = self.family.carry_leaves(cfg, 1) if self._has_carry else ()
        self._carry_spec = tuple((tuple(shape), jnp.dtype(dt).name)
                                 for shape, dt in leaves)
        self._carry_nbytes = sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for shape, dt in self._carry_spec)
        if prefix is not None and self._carry_nbytes > store.page_bytes:
            raise ValueError(
                "prefix_cache with a family whose carry does not fit a slot "
                "of the store: a prefix page is adoptable only with the "
                "carry at its boundary, every extent keeps a snapshot of it "
                f"in a slot as a page has, and a snapshot is "
                f"{self._carry_nbytes} B against a slot of "
                f"{store.page_bytes} B (ROADMAP.md, Queue 2)")
        # The kinds of the family's page (most families: one), the shape
        # of every leaf of a page, kind by kind, and where each kind's
        # leaves lie among them.
        self.kinds = self.family.page_kinds(cfg)
        if len(self.kinds) > 1 and prefix is not None:
            raise ValueError(
                "prefix_cache with a family whose page comes in kinds: an "
                "extent holds one page, and a prefix is adoptable only with "
                "the last pages of its window kinds (ROADMAP.md, Queue 2)")
        self._leaf_shapes = self.family.leaf_shapes(cfg, self.page_tokens)
        self._kind_leaves = self.family.kind_leaves(cfg)
        self._has_window = any(k.window is not None for k in self.kinds)
        # A chunk's expert count each, still on the device: a ship hands
        # the store its page where it lies and waits for nothing, so the
        # counts are looked at where the host waits for the device anyway
        # (:meth:`_note_pages`).
        self._pages_touched: list = []
        n_kinds = len(self.kinds)
        # The fused step's page pools, kept on the device between ticks
        # (see _batch_pool), one a kind: one array of rows (capacity, L,
        # KV, P, Hd) a leaf, the row of every (page_id, version) it holds,
        # least recently seated first, and the rows nothing was written to
        # yet (or whose page was dropped).
        self._pool: list = [None] * n_kinds
        self._pool_slots: list[dict] = [{} for _ in self.kinds]
        self._pool_free: list[list] = [[] for _ in self.kinds]
        # Pool capacities whose programs, and whose neighbours', have
        # already run (:meth:`_warm_pool`).
        self._pool_write_ready: list[set] = [set() for _ in self.kinds]
        # The seated sessions' tails, kept on the device between ticks
        # (see _seat_batch): one stack (L, b_pad, KV, P, Hd) a leaf, the
        # session in every seat (seats [0, len) are taken, a vacated one
        # is None until the next step closes it), and the stack widths
        # whose seat programs have already run.
        self._tails: tuple | None = None
        # A family with a carry: the seated sessions' carries, one stack a
        # leaf with the seat on axis 1, beside the tails and seat for seat.
        self._carry: tuple | None = None
        self._seats: list[_Session | None] = []
        self._seat_ready: set[int] = set()
        self._tab_cache: list = [(None, None)] * n_kinds
        # The entry of every shared extent's page some live session's
        # context holds, by page_id (see _Entry). The sessions' lists are
        # the references: when the last holder finishes, the entry and its
        # arrays go, as a finished session's private entries do, and a
        # dead extent, which nothing reclaims, keeps no arrays.
        self._shared = weakref.WeakValueDictionary()  # page_id -> _Entry
        self.queue: list[Request] = []
        self.active: list[_Session] = []
        # The session whose chunk a tick took last, while some session
        # that could prefill in that tick was not served: the next tick
        # starts after it (_prefill_turn).
        self._prefill_last: _Session | None = None
        self.results: list[SessionResult] = []
        # Pages of sessions that have stood up, until _free_ended frees
        # them together.
        self._ended: list = []
        # The tick's phase account (docs/OBSERVABILITY.md, "Serving tick
        # anatomy"): seconds since this engine was built, an entry a phase
        # (``_CHUNK`` ... ``_OUTSIDE``), summed from the ``dt`` of the spans
        # a tick opens anyway; the ticks whose books are closed; the last
        # tick's ``tick`` and ``tick.finish`` spans and the moment its books
        # were closed; and the sessions that emitted in this tick.
        self._acct = [0.0] * (len(GAP_PHASES) + 1)
        self._ticks = 0
        self._last_tick = None
        self._mark_t = 0.0
        self._emitted: list[_Session] = []
        # A stored page of each kind: its leaves stacked.
        self.page_shapes = tuple(
            (kind.n_leaves,) + self._leaf_shapes[sl.start]
            for kind, sl in zip(self.kinds, self._kind_leaves))
        expect = self.page_nbytes(cfg, self.page_tokens, store_dtype)
        if expect != store.page_bytes:
            raise ValueError(
                f"store page_bytes {store.page_bytes} != model page "
                f"{expect} (cfg/page_tokens/store_dtype mismatch)"
            )
        serving_metrics.publish(self.stats)
        # Warm boot (persist/, ROADMAP item 5): a store built over a
        # FrozenStore re-publishes the prefix extents a previous engine
        # incarnation persisted at close — cross-restart prefix hits
        # without recomputing a single prompt page. No backend (the
        # default everywhere) → byte-identical cold behavior.
        if self.prefix is not None:
            self.prefix.carry_nbytes = self._carry_nbytes
            if getattr(store, "frozen_backend", None) is not None:
                self.prefix.restore(store.frozen_backend)

    @staticmethod
    def page_nbytes(cfg, page_tokens: int,
                    store_dtype: str = "float32") -> int:
        """Size of one packed page (every leaf of it; of the largest
        kind, where ``cfg``'s family has several) — what the
        :class:`TieredPageStore` must be built with."""
        fam = family_of(cfg)
        kv, hd = fam.leaf_dims(cfg)
        return (max(kind.n_leaves * kind.layers
                    for kind in fam.page_kinds(cfg))
                * kv * page_tokens * hd * jnp.dtype(store_dtype).itemsize)

    @property
    def _pool_k(self):
        """The first pool's first leaf (the dense family's K rows), or
        None: its row count is that pool's capacity."""
        return None if self._pool[0] is None else self._pool[0][0]

    # -- submission / driving --------------------------------------------

    def submit(self, req: Request) -> None:
        # TTFT starts at SUBMIT, not admission: queue wait under
        # contention is exactly the latency a tenant experiences.
        req._submit_t = time.perf_counter()
        self.queue.append(req)

    def run(self) -> list[SessionResult]:
        """Drive to completion, a tick at a time (:meth:`_tick`): per
        tick — priority-ordered admission, one chunked-prefill slice per
        bulk-prefilling session, then ONE dispatch of the family's fused
        step advancing every seated session by one token."""
        while self.queue or self.active:
            self._tick()
        done, self.results = self.results, []
        return done

    def close(self) -> None:
        for sess in self.active:
            self._finish(sess, abandon=True)
        self._free_ended()
        self.active = []
        self._pool = [None] * len(self.kinds)
        self._pages_touched = []
        self._tails = None
        self._carry = None
        self._seats = []
        # Persist the prefix trie into the frozen tier (if one backs
        # the store) BEFORE the prefetcher drains: the pages are still
        # readable, and the next incarnation's __init__ restores them.
        if (self.prefix is not None
                and getattr(self.store, "frozen_backend", None) is not None):
            try:
                self.prefix.persist(self.store.frozen_backend)
            except OSError:
                pass  # a full/broken disk must never wedge shutdown
        self.prefetcher.close()
        serving_metrics.unpublish(self.stats)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- admission / prefill ---------------------------------------------

    def _admit(self, req: Request) -> _Session:
        # Prefix matching is INCREMENTAL (:meth:`_match_more`, probed at
        # every page boundary), not an admission-time lookup: sessions
        # admitted simultaneously still dedup against pages a sibling
        # publishes one tick later.
        sess = _Session(
            req, self._leaf_shapes, self.cfg.dtype,
            _zero_carry(self.family, self.cfg, 1), len(self.kinds))
        sess.admit_t = time.perf_counter()
        sess.admit_engine_s = sum(self._acct[:_OUTSIDE])
        sess.admit_tick = self._ticks
        self._note_pages_done(sess)
        return sess

    def _note_pages_done(self, sess: _Session) -> None:
        """Stamp the first moment after admission at which no whole page
        of prompt remains (``_bulk_prefill`` turns false once and stays
        false): called after admission, every adoption and every chunk."""
        if sess.pages_done_t is None and not self._bulk_prefill(sess):
            sess.pages_done_t = time.perf_counter()

    def _match_more(self, sess: _Session) -> None:
        """At a page boundary during prefill, adopt any shared extent
        covering the next chunk of this prompt instead of recomputing
        it. The LAST prompt token is always computed locally (its
        logits seed generation), so a whole-remainder match turns into
        a CoW adoption of all-but-one of its tokens."""
        if (self.prefix is None or not sess.chain_valid
                or sess.tail_len != 0):
            return
        last = self._adopt_extents(sess)
        if last is not None:
            if self._has_carry:
                self._restore_carry(sess, last)
            self.stats.note_adoption(restored=self._has_carry)

    def _adopt_extents(self, sess: _Session) -> SharedExtent | None:
        """Take every extent that extends the session's chain from where
        it stands; returns the last one taken."""
        P = self.page_tokens
        last = None
        while True:
            pc = sess.prompt_consumed
            rem = len(sess.prompt) - pc
            if rem <= 1:
                return last
            if rem > P:
                ext = self.prefix.child(sess.chain_parent,
                                        sess.prompt[pc:pc + P])
                if ext is None or ext.fill != P:
                    return last
                self.prefix.acquire(ext)
                sess.shared_refs.append(ext)
                sess.entries.append(self._entry_of(ext))
                sess.chain_parent = ext
                sess.pos += P
                sess.prompt_consumed += P
                sess.prefix_tokens_reused += P
                self.stats.note_tokens(P, phase="prefill")
                last = ext
                continue
            # 2 <= rem <= P: the prompt's tail chunk. Adopt all but the
            # final token by copy-on-write when a shared extent holds
            # exactly these tokens (full page or partial alike). A full
            # page's carry snapshot stands after its last token, where this
            # adopter cannot resume: with a carry the page program takes
            # the whole page.
            ext = self.prefix.child(sess.chain_parent, sess.prompt[pc:])
            if (ext is not None and ext.fill > 1
                    and not (self._has_carry and ext.fill == P)):
                self._adopt_partial(sess, ext, upto=rem - 1)
                sess.prompt_consumed += rem - 1
                self.stats.note_tokens(rem - 1, phase="prefill")
                last = ext
            return last

    def _adopt_partial(self, sess: _Session, ext: SharedExtent,
                       upto: int) -> None:
        """Copy-on-write adoption of a partial shared tail: the session
        continues into a private clone, loading the first ``upto``
        tokens' KV from the shared bytes (the divergence point). The
        shared extent keeps its reference until the session ends."""
        self.prefix.acquire(ext)
        sess.shared_refs.append(ext)
        clone = self.store.cow(ext.page)
        sess.tails = self._unpack(self.store.read_page(clone))
        sess.tail_len = upto
        sess.page_toks = list(ext.tokens[:upto])
        sess.pos += upto
        sess.prefix_tokens_reused += upto
        sess.entries.append(_Entry(page=clone, pending_fill=True))
        # Chain continuity: the completed clone page will extend the
        # node ABOVE the partial (its full token tuple replaces the
        # partial's).
        sess.chain_parent = ext.parent

    def _snapshot(self, sess: _Session) -> Page:
        """The session's carry as it stands, into a slot of the store: what
        a prefix extent published at this boundary keeps beside its page.
        A copy: the programs go on donating the carry itself."""
        with GLOBAL_TRACER.span("prefix.snapshot"):
            carry = (sess.carry if sess.seat is None
                     else _seat_read_jit(self._carry, np.int32(sess.seat)))
            page = self.store.alloc_page(_carry_pack_jit(carry), shared=True)
            self.stats.note_carry_snapshot()
        return page

    def _restore_carry(self, sess: _Session, ext: SharedExtent) -> None:
        """Adoption's other half: the session goes on from the carry the
        last adopted extent keeps. Fresh arrays, never the snapshot's own
        bytes."""
        with GLOBAL_TRACER.span("prefix.restore"):
            data = jnp.asarray(np.array(self.store.read_page(ext.carry),
                                        copy=True))
            carry = _carry_unpack_jit(data, self._carry_spec)
            if sess.seat is None:
                sess.carry = carry
            else:
                self._carry = _seat_write_jit(self._carry, carry,
                                              np.int32(sess.seat))

    # -- residency / prefetch --------------------------------------------

    def _unpack(self, data: np.ndarray, kind: int = 0) -> tuple:
        shape = self.page_shapes[kind]
        packed = from_bytes(jnp.asarray(np.array(data, copy=True)),
                            shape, self.store_dtype)
        dt = jnp.dtype(self.cfg.dtype)
        return tuple(packed[i].astype(dt) for i in range(shape[0]))

    def _resident(self, e: _Entry) -> bool:
        return (e.arrays is not None and e.version == e.page.version
                and e.page.tier == Tier.HOT)

    def _entry_of(self, ext: SharedExtent) -> _Entry:
        """The one entry of a shared extent's page, for a session that
        takes the page into its context: the entry its live holders list,
        arrays and all, or a fresh one whose arrays the next residency
        pass builds once, for everyone who takes the page after."""
        entry = self._shared.get(ext.page.page_id)
        if entry is None:
            entry = _Entry(page=ext.page, extent=ext)
            self._shared[ext.page.page_id] = entry
        else:
            self.stats.note_arrays(shared=1)
        return entry

    def _rebuild(self, e: _Entry, data: np.ndarray) -> None:
        """Build the entry's arrays from its page's bytes in the store."""
        e.arrays = self._unpack(data, e.kind)
        e.version = e.page.version
        self.stats.note_arrays(rebuilt=1)

    def _prefetch_for(self, sess: _Session) -> None:
        for e in sess.entries:
            if (not e.pending_fill and not self._resident(e)
                    and e.page.tier != Tier.HOT):
                self.prefetcher.submit(e.page)

    def _ensure_resident(self, sess: _Session) -> None:
        for e in sess.entries:
            if e.pending_fill:
                continue
            # Hit = the page is in the fast tier at schedule time; a
            # miss is a real fetch from warm/cold (the stall path).
            hot = e.page.tier == Tier.HOT
            self.stats.note_lookup(hot)
            if self._resident(e):
                self.store.touch(e.page)
                continue
            if hot:
                # No holder has built the arrays at this version (an extent
                # nobody alive held / a page moved back up or rewritten):
                # rebuild from the fast tier — no stall.
                self._rebuild(e, self.store.read_page(e.page))
                continue
            data = self._obtain(sess, e.page)
            self.store.promote(e.page, data=data[0], version=data[1])
            self._rebuild(e, data[0])
            if data[2] is not None:
                self.prefetcher.recycle(data[2])

    def _recycle_late(self, fut) -> None:
        """A prefetch abandoned past the step budget eventually lands:
        return its buffer to the pool instead of leaking it."""
        try:
            buf, _version, _ok = fut.result(timeout=0)
        except Exception:  # noqa: BLE001 — a failed late fetch has no buffer
            return
        if buf is not None:
            self.prefetcher.recycle(buf)

    def _obtain(self, sess: _Session, page: Page):
        """Page bytes + the version they correspond to: a completed
        prefetch is free; waiting on one (or faulting with none issued)
        is recorded as stall time."""
        fut = self.prefetcher.take(page.page_id)
        if fut is not None:
            already = fut.done()
            t0 = time.perf_counter()
            # A straggling prefetch is waited on at most the remaining
            # step budget (unbudgeted: 120 s): past it
            # the wait degrades to a synchronous fault below — pure
            # stall accounting, never a wedged decode step. The
            # abandoned future recycles its buffer when it finally
            # lands.
            wait_s = 120.0
            bud = self._step_budget
            if bud is not None:
                wait_s = min(wait_s, max(bud.remaining_s(), 1e-3))
            import concurrent.futures as _cf

            try:
                buf, version, ok = fut.result(timeout=wait_s)
            except (_cf.TimeoutError, TimeoutError):
                waited = time.perf_counter() - t0
                sess.stall_s += waited
                self.stats.note_stall(waited)
                obs_journal.record(
                    "prefetch_stall", page_id=page.page_id,
                    wait_ms=round(waited * 1e3, 3), degraded=True,
                )
                fut.add_done_callback(
                    lambda f: self._recycle_late(f)
                )
                buf, version, ok = None, -1, False
            except Exception as e:  # noqa: BLE001 — fall back to a fault
                printd("serving: prefetch failed (%s); faulting", e)
                buf, version, ok = None, -1, False
            waited = time.perf_counter() - t0
            if ok and version == page.version:
                self.stats.note_prefetch(completed=True)
                if not already:
                    # Prefetch lost the race: the decode sat waiting.
                    sess.stall_s += waited
                    self.stats.note_stall(waited)
                    obs_journal.record("prefetch_stall",
                                       page_id=page.page_id,
                                       wait_ms=round(waited * 1e3, 3))
                return (buf[:page.nbytes], version, buf)
            if buf is not None:
                self.prefetcher.recycle(buf)
        # Page fault: no (usable) prefetch — the whole fetch is stall.
        t0 = time.perf_counter()
        version = page.version
        data = np.array(self.store.read_page(page), copy=True)
        stall = time.perf_counter() - t0
        sess.stall_s += stall
        self.stats.note_stall(stall)
        obs_journal.record("prefetch_stall", page_id=page.page_id,
                           wait_ms=round(stall * 1e3, 3), fault=True)
        return (data, version, None)

    def _context(self, sess: _Session) -> tuple:
        """The session's paged context, a leaf at a time, kind by kind:
        the arrays of the kind's pages joined along the token axis."""
        pages = [[] for _ in self.kinds]
        for e in sess.entries:
            if not e.pending_fill:
                pages[e.kind].append(e.arrays)
        if self.family.context is not None:
            return self.family.context(pages, self.cfg, self.page_tokens)
        ctx = []
        for held, leaves in zip(pages, self._kind_leaves):
            for i, shape in enumerate(self._leaf_shapes[leaves]):
                if held:
                    ctx.append(jnp.concatenate([a[i] for a in held], axis=3))
                else:
                    ctx.append(jnp.zeros(shape[:3] + (0,) + shape[4:],
                                         jnp.dtype(self.cfg.dtype)))
        return tuple(ctx)

    # -- decode -----------------------------------------------------------

    def _tick(self) -> None:
        """One scheduler tick under one root span. Its six children
        (``tick.admit``, ``tick.match``, ``serve_prefill_chunk``,
        ``tick.select``, ``serve_batch_step``, ``tick.finish``) cover it
        without remainder and every span name has exactly one parent, so
        self time falls out of per-name totals (docs/OBSERVABILITY.md,
        "Serving tick anatomy")."""
        span = GLOBAL_TRACER.span
        acct = self._acct
        with span("tick") as tick:
            named = sum(acct[:_FINISH])    # chunk, build, device, scatter
            with span("tick.admit"):
                # Admission is priority-aware: PRIO_HIGH requests seat
                # first when the queue outruns max_active (stable within
                # a class, so equal-priority arrival order is preserved).
                if self.queue and len(self.active) < self.max_active:
                    self.queue.sort(
                        key=lambda r: -getattr(r, "priority", PRIO_NORMAL)
                    )
                    while (self.queue
                           and len(self.active) < self.max_active):
                        self.active.append(self._admit(self.queue.pop(0)))
                if self.step_budget_ms:
                    from oncilla_tpu.resilience import timebudget

                    self._step_budget = timebudget.Budget.from_ms(
                        self.step_budget_ms
                    )
            with span("tick.match"):
                prefetch_on = self.prefetcher.mode != "off"
                for sess in self.active:
                    self._match_more(sess)
                    self._note_pages_done(sess)
                    if prefetch_on:
                        self._prefetch_for(sess)
            # Chunked prefill: a long prompt admits whole pages a tick (a
            # chunk of up to the family's `chunk_pages`, one dispatch of its
            # page program) instead of streaming its tokens through the
            # shared batch — the batch never stalls behind a prompt.
            # Chunks are taken in turn, from after the session served
            # last, until the tick's pages reach max(n, most) for n
            # sessions prefilling: never fewer than one page each of them.
            # With a prefix cache a chunk is one page, so that the re-probe
            # before it can adopt what a sibling just published.
            most = 1 if self.prefix is not None else self.family.chunk_pages
            ready = [s for s in self.active if self._bulk_prefill(s)]
            budget, taken, chunked = max(len(ready), most), 0, False
            for sess in self._prefill_turn(ready):
                if taken >= budget:
                    break
                self._prefill_last = sess
                # Span per chunk: its phases (and any cold-tier dcn fetch
                # spans the chunk faults on) tree under it.
                with span("serve_prefill_chunk") as chunk:
                    # Re-probe the prefix cache first: a session earlier
                    # in this same tick may have shipped (and registered)
                    # exactly the page this one is about to compute —
                    # matching here is what lets identical prompts
                    # converge on shared pages (and CoW partial adoption)
                    # instead of prefilling in lockstep.
                    with span("prefill.match"):
                        self._match_more(sess)
                    if self._bulk_prefill(sess):
                        taken += self._prefill_chunk(sess, most)
                        chunked = True
                    self._note_pages_done(sess)
                acct[_CHUNK] += chunk.dt
                sess.own_chunk_s += chunk.dt
            else:
                self._prefill_last = None   # every one served: from the top
            with span("tick.select"):
                batch = self._select_batch(allow_force=not chunked)
            if batch:
                self._batch_step(batch)
            with span("tick.finish") as finish:
                for sess in self.active:
                    if sess.done:
                        self._finish(sess)
                self._free_ended()
                self.active = [s for s in self.active if not s.done]
                self._close_books(tick, finish, sum(acct[:_FINISH]) - named)
        self._last_tick = (tick, finish)

    def _close_books(self, tick, finish, named: float) -> None:
        """The end of a tick, inside ``tick.finish``: bring the account up
        to this moment and put every token the tick made down to the phases
        its gap spanned. ``named`` is what the tick's chunk, build, device
        and scatter spans added; ``finish`` runs until now, and what is
        left of the tick is ``sched``. The one clock read a tick: a gap runs
        from one tick's reading to another's, which is where the caller of
        :meth:`_tick` stamps its tokens, so the entries of the account add
        up to the wall time between two readings without remainder. What
        the last tick did after its reading is known now that its spans
        are closed: the rest of its ``tick.finish`` (these books) is
        ``finish`` and the closing of ``tick`` is ``sched``, so each phase
        is the total of its spans; from its end to this tick's start the
        engine was not running: ``outside``."""
        now = time.perf_counter()
        acct = self._acct
        if self._last_tick is not None:
            last, last_finish = self._last_tick
            finished = last_finish.t0 + last_finish.dt
            ended = last.t0 + last.dt
            acct[_FINISH] += finished - self._mark_t
            acct[_SCHED] += ended - finished
            acct[_OUTSIDE] += tick.t0 - ended
        acct[_FINISH] += now - finish.t0
        acct[_SCHED] += finish.t0 - tick.t0 - named
        self._mark_t = now
        self._ticks = n = self._ticks + 1
        if not self._emitted:
            return
        # The sessions that last emitted in one tick hold one mark, and
        # their gaps are one gap: a steady batch costs one subtraction.
        mark = (tuple(acct), n)
        engine_s = sum(acct[:_OUTSIDE])
        same: dict[int, list] = {}      # id(mark) -> [mark, sessions]
        firsts = []
        for sess in self._emitted:
            before, sess.gap_mark = sess.gap_mark, mark
            if before is not None:
                if id(before) in same:
                    same[id(before)][1] += 1
                else:
                    same[id(before)] = [before, 1]
            elif sess.ttft_parts is not None:
                # A first token opens the session's first gap and closes
                # none: that wait is the TTFT's (_note_first_token).
                firsts.append((engine_s - sess.admit_engine_s,
                               n - sess.admit_tick, sess.unseated_ticks,
                               sess.own_chunk_s, sess.ttft_parts["queue_s"]))
        self.stats.note_gaps(
            [([now_s - then_s for now_s, then_s in zip(acct, then)],
              n - tick_no, count) for (then, tick_no), count in same.values()],
            firsts)
        self._emitted = []

    def _note_first_token(self, sess: _Session) -> None:
        """TTFT: observed once per session, on its first emitted token
        (submit -> first visible output), split where it was spent:
        waiting for ``max_active``, whole pages (adoption and one chunk a
        tick), and the sub-page remainder riding the fused step. A
        whole-page prompt gets its token from its last chunk: no tail."""
        if len(sess.out) != 1 or not sess.submit_t or sess.ttft_noted:
            return
        sess.ttft_noted = True
        now = time.perf_counter()
        if sess.pages_done_t is None:
            sess.pages_done_t = now
        sess.ttft_parts = {
            "queue_s": sess.admit_t - sess.submit_t,
            "chunk_s": sess.pages_done_t - sess.admit_t,
            "tail_s": now - sess.pages_done_t,
            "unseated_ticks": sess.unseated_ticks,
        }
        ttft_s = now - sess.submit_t
        self.stats.note_ttft(ttft_s, **sess.ttft_parts)
        obs_journal.record("ttft", tenant=sess.req.tenant,
                           ttft_s=round(ttft_s, 6), **sess.ttft_parts)

    def _bulk_prefill(self, sess: _Session) -> bool:
        """True while >= one whole page of prompt remains and the tail is
        page-aligned — the state chunked prefill consumes."""
        return (not sess.done and sess.tail_len == 0
                and len(sess.prompt) - sess.prompt_consumed
                >= self.page_tokens)

    def _prefill_turn(self, ready: list[_Session]) -> list[_Session]:
        """The sessions that can prefill (``ready``, in admission order) in
        the order the tick takes them: from the one after the session
        served last, round to it; from the first where every one was served
        last tick."""
        try:
            at = self.active.index(self._prefill_last)
        except ValueError:
            return ready
        served = {id(s) for s in self.active[:at + 1]}
        k = sum(id(s) in served for s in ready)
        return ready[k:] + ready[:k]

    def _prefill_chunk(self, sess: _Session, most: int = 1) -> int:
        """Teacher-force the next ``min(most, whole pages left)`` pages of
        prompt in one dispatch of the family's page program, ship them a
        page at a time, and emit the seed token when the prompt completes.
        Returns the pages taken."""
        span = GLOBAL_TRACER.span
        P = self.page_tokens
        pages = min(most, (len(sess.prompt) - sess.prompt_consumed) // P)
        with span("prefill.residency"):
            self._ensure_resident(sess)
            ctx = self._context(sess)
            held = [0] * len(self.kinds)
            for e in sess.entries:
                if not e.pending_fill:
                    held[e.kind] += P
            self.stats.note_kv_page(sum(
                kind.layers * (n if kind.window is None
                               else min(n, kind.window))
                for kind, n in zip(self.kinds, held)))
            if sess.seat is not None:
                self._unseat(sess)
        with span("prefill.dispatch"):
            pc = sess.prompt_consumed
            chunk = sess.prompt[pc:pc + pages * P]
            # Where the chunk starts, and a kind where its context does.
            meta = jnp.asarray(
                [sess.pos] + [n * P for n in sess.dropped], jnp.int32)
            made = None
            if most > 1:
                # A chunk of fewer pages fills the rest with padding: one
                # program a context length, whatever the count.
                tokens = chunk + [0] * ((most - pages) * P)
                logits, made, touched = self.family.page(
                    self.params, jnp.asarray([tokens], jnp.int32), meta, ctx,
                    sess.tails, self.cfg, pages=np.int32(pages))
            else:
                args = (self.params, jnp.asarray([chunk], jnp.int32), meta,
                        ctx, sess.tails, self.cfg)
                if self._has_carry:
                    logits, sess.tails, touched, sess.carry = (
                        self.family.page(*args, sess.carry))
                else:
                    logits, sess.tails, touched = self.family.page(*args)
        self.stats.note_prefill_chunk(pages)
        obs_journal.record("prefill_chunk", tenant=sess.req.tenant,
                           tokens=pages * P, pos=sess.pos + pages * P)
        for j in range(pages):
            sess.pos += P
            sess.tail_len = P
            sess.page_toks = chunk[j * P:(j + 1) * P]
            sess.prompt_consumed += P
            self.stats.note_tokens(P, phase="prefill")
            if sess.prompt_consumed == len(sess.prompt):
                with span("prefill.sync"):
                    last = logits[0, -1 if made is None else j]
                    sess.out.append(int(jnp.argmax(last)))
                    self._emitted.append(sess)
                    if self.keep_logits:
                        sess.logits.append(np.asarray(last))
                self._note_first_token(sess)
                if len(sess.out) == sess.req.max_new_tokens:
                    sess.done = True
            with span("prefill.ship"):
                if made is None:
                    self._ship(sess)
                else:
                    if j == 0:
                        # The chunk's pages go into the store together,
                        # in the first page's ship.
                        placed = self._place_chunk(made, pages)
                    for k, page in enumerate(placed[j]):
                        sess.entries.append(_Entry(
                            page=page, arrays=made[j][self._kind_leaves[k]],
                            version=page.version, kind=k))
                    if j == pages - 1:
                        sess.reset_tail()
                if touched is not None and j == 0:
                    self._pages_touched.append(touched)
                self._match_more(sess)
        # After the chunk's last ship: a window-kind page leaves only once
        # no later query of the chunk reads it.
        if self._has_window:
            with span("prefill.drop"):
                self._drop_passed(sess)
        return pages

    def _place_chunk(self, made: tuple, pages: int) -> list:
        """The first ``pages`` of a chunk's page tails ``made`` as stored
        pages, a tuple of one a kind for each page in order: one pack of
        the whole chunk and one :meth:`TieredPageStore.alloc_pages` a kind
        (what :meth:`_ship` does a page at a time for a session's private
        pages)."""
        packed = _pack_chunk_jit(
            tuple(tuple(tail[leaves] for tail in made)
                  for leaves in self._kind_leaves),
            self.store_dtype)
        by_kind = [self.store.alloc_pages(rows, pages) for rows in packed]
        for kind in self.kinds:
            if kind.window is not None:
                self.stats.note_window(shipped=pages)
        return list(zip(*by_kind))

    def _yields_cold(self, sess: _Session) -> bool:
        """True when a seat should be given up this tick: some context
        page is off the hot tier with its prefetch still in flight."""
        if self.prefetcher.mode == "off":
            return False  # nothing is ever in flight: faults are sync
        for e in sess.entries:
            if (not e.pending_fill and not self._resident(e)
                    and e.page.tier != Tier.HOT
                    and self.prefetcher.pending(e.page.page_id)):
                return True
        return False

    def _select_batch(self, allow_force: bool) -> list[_Session]:
        """Admission-aware seating for one fused step: cold sessions
        yield (their prefetch finishes off-batch), the rest seat in
        priority order up to ``max_batch``; losers of either contention
        are counted as preempts. ``allow_force`` guarantees progress —
        when nothing else ran this tick the best yielded session is
        seated anyway and takes its fault synchronously."""
        runnable = [s for s in self.active
                    if not s.done and not self._bulk_prefill(s)]
        ready, yielded = [], []
        for sess in runnable:
            if self._yields_cold(sess):
                yielded.append(sess)
                self.stats.note_preempt("cold_page")
            else:
                ready.append(sess)
        if not ready and yielded and allow_force:
            yielded.sort(key=lambda s: -s.priority)
            ready = [yielded[0]]
        ready.sort(key=lambda s: -s.priority)
        for sess in ready[self.max_batch:]:
            self.stats.note_preempt("slot")
        batch = ready[:self.max_batch]
        for sess in runnable:
            # Request anatomy: a tick a runnable session spent without a
            # seat while still waiting for its first token.
            if not sess.out and sess not in batch:
                sess.unseated_ticks += 1
        return batch

    def _ensure_resident_batch(self, batch: list[_Session]) -> None:
        """Residency for one fused tick: every miss's bytes are obtained
        first, then all promotions install under ONE watermark sweep
        (:meth:`TieredPageStore.promote_many`) — B sessions' faults
        cannot thrash each other's freshly promoted pages mid-build."""
        items, installs = [], []
        seen: dict[int, tuple] = {}
        for sess in batch:
            for e in sess.entries:
                if e.pending_fill:
                    continue
                hot = e.page.tier == Tier.HOT
                self.stats.note_lookup(hot)
                if self._resident(e):
                    self.store.touch(e.page)
                    continue
                if hot:
                    self._rebuild(e, self.store.read_page(e.page))
                    continue
                pid = e.page.page_id
                if pid not in seen:
                    # A page two sessions of the batch hold is one entry:
                    # obtained, promoted and rebuilt once.
                    got = self._obtain(sess, e.page)
                    seen[pid] = got
                    items.append((e.page, got[0], got[1]))
                    installs.append((e, got))
        if items:
            self.store.promote_many(items)
        for e, got in installs:
            self._rebuild(e, got[0])
        for got in seen.values():
            if got[2] is not None:
                self.prefetcher.recycle(got[2])

    def _batch_pool(self, batch: list[_Session]):
        """The tick's page pools + per-session block tables, one of each a
        kind of the family's page (:meth:`_kind_pool`). Returns, each a
        list over the kinds: the pools (a tuple of leaves each), the block
        tables ``(len(batch), MP)`` and, session by session, the keys the
        tables' rows stand for."""
        n = len(self.kinds)
        rows: list[dict] = [{} for _ in range(n)]
        keys: list[list] = [[] for _ in range(n)]
        for sess in batch:
            mine = [[] for _ in range(n)]
            for e in sess.entries:
                if e.pending_fill:
                    continue
                key = (e.page.page_id, e.version)
                rows[e.kind].setdefault(key, e.arrays)
                mine[e.kind].append(key)
            for k in range(n):
                keys[k].append(mine[k])
        tables = [self._kind_pool(k, rows[k], keys[k]) for k in range(n)]
        return list(self._pool), tables, keys

    def _kind_pool(self, k: int, rows: dict, keys: list) -> np.ndarray:
        """Bring kind ``k``'s pool up to the batch and return its block
        table. The pool is device state that outlives the tick: every
        distinct resident page of the batch holds one row of a (capacity,
        L, KV, P, Hd) pool, one such array a leaf of the kind (a shared
        prefix page is one row however many sessions reference it) under
        its (page_id, version), and table[b] lists session b's rows. A
        page that has a row keeps it, with no device work; a page without
        one takes a free row, or the row of the page seated longest ago
        that this batch does not reference, and the tick's new pages are
        written there in place, up to ``_POOL_GROUP`` a dispatch
        (:func:`_pool_write_jit`, in power-of-two groups).
        So a session that loses its seat for a tick finds its rows again.
        ``capacity`` and MP snap to power-of-two buckets of this batch's
        rows; when the capacity bucket changes, the rows are carried over
        on the device in one dispatch (:meth:`_new_pool`) and only the
        pages that had no row are written. ``rows`` is the batch's distinct
        pages by key, ``keys`` each session's keys in context order."""
        max_pages = max((len(t) for t in keys), default=0)
        mp = _pow2(max_pages) if max_pages else 0
        capacity = _pow2(len(rows)) if rows else 1
        first = self._pool[k] is None
        rebuilt = first or self._pool[k][0].shape[0] != capacity
        carried = self._new_pool(k, capacity, rows) if rebuilt else 0
        slots, free = self._pool_slots[k], self._pool_free[k]
        fresh = []
        for key in rows:
            if key in slots:
                slots[key] = slots.pop(key)  # seated now: reclaimed last
            else:
                fresh.append(key)
        for key in fresh:
            # Every seated key is behind the unseated ones by now, and the
            # batch has at most `capacity` keys: with no row free, the
            # oldest key is one this batch does not reference.
            slots[key] = free.pop() if free else slots.pop(next(iter(slots)))
        groups = _pow2_chunks(len(fresh), _POOL_GROUP)
        at = 0
        for n in groups:
            group, at = fresh[at:at + n], at + n
            self._pool[k] = _pool_write_jit(
                self._pool[k], tuple(rows[key] for key in group),
                np.asarray([slots[key] for key in group], np.int32))
        self.stats.note_pool(
            reused=len(rows) - len(fresh), written=len(fresh),
            rebuilt=rebuilt, group_writes=len(groups),
            gathers=int(rebuilt and not first), carried=carried)
        table = np.zeros((len(keys), mp), np.int32)
        for b, trow in enumerate(keys):
            table[b, :len(trow)] = [slots[key] for key in trow]
        return table

    def _new_pool(self, k: int, capacity: int, rows: dict) -> int:
        """Give kind ``k`` a pool of ``capacity`` rows. The rare path: the
        first pool, which is zeros with every row free, and a crossing of a
        power-of-two row count, which makes the new pool from the old one
        on the device in ONE dispatch (:func:`_pool_gather_jit`): on growth
        every key keeps a row, on a shrink the keys of ``rows``, this
        batch's, do and the others lose theirs; the keys are renumbered
        from row 0 in the order they had, least recently seated first. A
        row no key owns holds whatever the gather left there: the step
        reads a row only through a table entry. Old and new pool are alive
        together while the gather runs. Returns the rows carried over."""
        self._warm_pool(k, capacity)
        old, slots = self._pool[k], self._pool_slots[k]
        if old is None:
            kept = []
            self._pool[k] = self._zero_pool(k, capacity)
        else:
            grown = capacity > old[0].shape[0]
            kept = [key for key in slots if grown or key in rows]
            idx = np.zeros(capacity, np.int32)
            idx[:len(kept)] = [slots[key] for key in kept]
            self._pool[k] = _pool_gather_jit(old, idx)
        self._pool_slots[k] = dict(zip(kept, range(len(kept))))
        self._pool_free[k] = list(range(capacity - 1, len(kept) - 1, -1))
        return len(kept)

    def _zero_pool(self, k: int, capacity: int) -> tuple:
        """Zeros of kind ``k``'s pool at ``capacity`` rows."""
        dt = jnp.dtype(self.cfg.dtype)
        return tuple(jnp.zeros((capacity, page[0]) + page[2:], dt)
                     for page in self._leaf_shapes[self._kind_leaves[k]])

    def _warm_pool(self, k: int, capacity: int) -> None:
        """No program may be built when a page is first written in place or
        a crossing first carries the rows over, at whatever tick that is:
        when a capacity is first reached, the group writes at it, at half
        and at twice it and the gathers between them run once here, on one
        scratch pool of zeros handed from call to call. (A crossing that
        skips a capacity builds its gather where it falls.)"""
        ready = self._pool_write_ready[k]
        if capacity in ready:
            return
        # A neighbour that was reached has run what lies between the two.
        sizes = [n for n in (capacity // 2, capacity, 2 * capacity)
                 if n and (n == capacity or n not in ready)]
        dt = jnp.dtype(self.cfg.dtype)
        page = tuple(jnp.zeros(shape, dt)
                     for shape in self._leaf_shapes[self._kind_leaves[k]])
        scratch = self._zero_pool(k, sizes[0])
        for n in sizes[1:] + sizes[-2::-1]:          # up, then down again
            g = _POOL_GROUP
            while g:                                 # every group size
                scratch = _pool_write_jit(scratch, (page,) * g,
                                          np.zeros(g, np.int32))
                g //= 2
            scratch = _pool_gather_jit(scratch, np.zeros(n, np.int32))
        ready.add(capacity)

    def _batch_step(self, batch: list[_Session]) -> None:
        """ONE fused jit dispatch advancing every seated session by one
        token, then per-session scatter of logits/tails/bookkeeping.
        Runs under the ``serve_batch_step`` span; its ``step.*`` children
        cover it."""
        span = GLOBAL_TRACER.span
        P = self.page_tokens
        cfg = self.cfg
        acct = self._acct       # each child's dt, into its phase
        with span("serve_batch_step") as step:
            with span("step.residency") as part:
                self._ensure_resident_batch(batch)
            acct[_BUILD] += part.dt
            with span("step.args") as part:
                # Rows in seat order from here on: row b is seat b.
                joined, moved = self._seat_batch(batch)
                batch = list(self._seats)
            acct[_BUILD] += part.dt
            if self._has_carry:
                with span("step.carry") as part:
                    self._seat_carries(joined, moved)
                acct[_BUILD] += part.dt
            with span("step.pool") as part:
                pools, tables, keys = self._batch_pool(batch)
            acct[_BUILD] += part.dt
            with span("step.args") as part:
                b_pad = _pow2(len(batch))
                toks, metas, prefills = [], [], []
                layers = [kind.layers for kind in self.kinds]
                held = whole = 0
                for b, sess in enumerate(batch):
                    if sess.prompt_consumed < len(sess.prompt):
                        tok = sess.prompt[sess.prompt_consumed]
                        sess.prompt_consumed += 1
                        prefill = True
                        self.stats.note_tokens(1, phase="prefill")
                        if (self._has_carry
                                and sess.prompt_consumed == len(sess.prompt)
                                and self._publishes_partial(sess, 1)):
                            # Where an adopter of the partial tail resumes:
                            # before the prompt's last token.
                            sess.boundary = self._snapshot(sess)
                    else:
                        tok = sess.out[-1] if sess.out else sess.prompt[-1]
                        prefill = False
                    toks.append(tok)
                    prefills.append(prefill)
                    # A row: where it is, then a kind how long its
                    # context is and where that starts.
                    row = [sess.pos, sess.tail_len]
                    for k, n_layers in enumerate(layers):
                        pages, gone = len(keys[k][b]), sess.dropped[k]
                        row += [pages * P, gone * P]
                        held += n_layers * pages
                        whole += n_layers * (pages + gone)
                    metas.append(row)
                self.stats.note_kv(held * P, whole * P)
                pad_b = b_pad - len(batch)
                toks += [0] * pad_b
                metas += [[0] * len(metas[0])] * pad_b
                tabs = []
                for table in tables:
                    tab = np.zeros((b_pad, table.shape[1]), np.int32)
                    tab[:len(batch)] = table
                    tabs.append(((tab.shape, tab.tobytes()), tab))
            acct[_BUILD] += part.dt
            with span("step.dispatch") as part:
                for k, (tab_key, tab) in enumerate(tabs):
                    if self._tab_cache[k][0] != tab_key:
                        self._tab_cache[k] = (tab_key, jnp.asarray(tab))
                on_device = [cached for _, cached in self._tab_cache]
                # The stack is donated; the step hands it back with every
                # row's token in place. A step that raises hands nothing
                # back: nobody holds a seat of a stack that is gone.
                args = (self.params, jnp.asarray(toks, jnp.int32),
                        jnp.asarray(metas, jnp.int32), len(batch),
                        tuple(leaf for pool in pools for leaf in pool),
                        on_device[0] if len(pools) == 1
                        else tuple(on_device),
                        self._tails, cfg)
                try:
                    if self._has_carry:
                        logits, self._tails, touched, self._carry = (
                            self.family.step(*args, self._carry))
                    else:
                        logits, self._tails, touched = self.family.step(
                            *args)
                except BaseException:
                    self.store.free_pages(
                        [sess.boundary for sess in batch if sess.boundary])
                    for sess in batch:
                        sess.seat = None
                        sess.boundary = None
                    self._tails = None
                    self._carry = None
                    self._seats = []
                    raise
            acct[_DEVICE] += part.dt
            with span("step.sync") as part:
                # One fused greedy argmax + host transfer for the whole
                # batch (row b is bitwise jnp.argmax(logits[b]) — same
                # bits, same first-max tie-break); doubles as the step's
                # device sync: the host waits on the device here. A family
                # with experts hands its count back in the same transfer.
                if touched is None:
                    best = np.asarray(jnp.argmax(logits, axis=-1))
                else:
                    best, touched = jax.device_get(
                        (jnp.argmax(logits, axis=-1), touched))
                    self.stats.note_moe_step(
                        int(touched),
                        len(batch) * self.family.assignments_per_token(cfg))
                self._note_pages()
                kept = np.asarray(logits) if self.keep_logits else None
                # The step's own books, inside the span that ends it.
                dt = time.perf_counter() - step.t0
                self.stats.note_batch_step(len(batch), dt)
                obs_journal.record(
                    "batch_step", size=len(batch), pad=b_pad,
                    pages=int(tables[0].shape[1]), ms=round(dt * 1e3, 3),
                )
            acct[_DEVICE] += part.dt
            with span("step.scatter") as part:
                for b, (sess, tok, prefill) in enumerate(
                        zip(batch, toks, prefills)):
                    sess.pos += 1
                    sess.tail_len += 1
                    sess.page_toks.append(int(tok))
                    emit = (not prefill
                            or sess.prompt_consumed == len(sess.prompt))
                    if emit:
                        sess.out.append(int(best[b]))
                        self._emitted.append(sess)
                        if kept is not None:
                            sess.logits.append(kept[b])
                        self._note_first_token(sess)
                        if not prefill:
                            self.stats.note_tokens(1)
                    if sess.tail_len == P:
                        with span("step.ship"):
                            # One read of the seat; the seat stays the
                            # session's, empty.
                            self._ship(sess)
                            self._match_more(sess)
                        if self._has_window:
                            with span("step.drop"):
                                self._drop_passed(sess)
                    elif (self.share_partials and prefill
                          and sess.prompt_consumed == len(sess.prompt)):
                        with span("step.publish"):
                            self._publish_partial(sess)
                    if len(sess.out) > sess.req.max_new_tokens:
                        raise AssertionError("overran max_new_tokens")
                    if len(sess.out) == sess.req.max_new_tokens:
                        sess.done = True
            acct[_SCATTER] += part.dt

    def _seat_batch(self, batch: list[_Session]) -> tuple[list, list]:
        """Seat ``batch`` for one fused step: afterwards ``self._seats``
        is the batch, seat by seat, and ``self._tails`` holds every
        session's tail in its seat. The stack is device state that
        outlives the tick, and a session that was seated in the last step
        and still is costs nothing, whoever else shipped, published,
        finished or joined. A seat changes hands at one dispatch: a
        session that leaves alive reads its tail out (:meth:`_unseat`), one
        that joins with tokens in its tail has it written into its seat
        (one that joins empty just sits down: the step reads a row that
        enters with tail_len 0 as zeros), and the step counts rows
        [0, n_real) as sessions, so a seat vacated in the middle is taken
        by a joiner or by the last seat, moved. A change of ``b_pad``
        unseats everybody into a fresh stack (:meth:`_new_tails`).
        Returns the sessions that sat down and the (from, to) seats that
        moved, for :meth:`_seat_carries`."""
        b_pad = _pow2(len(batch))
        rebuilt = self._tails is None or self._tails[0].shape[1] != b_pad
        staying = set() if rebuilt else {id(sess) for sess in batch}
        for sess in self._seats:
            if sess is not None and id(sess) not in staying:
                self._unseat(sess)
        if rebuilt:
            self._new_tails(b_pad)
        seats = self._seats
        holes = [b for b, sess in enumerate(seats) if sess is None]
        written = 0
        joined, moved = [], []
        for sess in batch:
            if sess.seat is not None:
                continue                # in its seat since the last step
            joined.append(sess)
            if holes:
                sess.seat = holes.pop(0)
                seats[sess.seat] = sess
            else:
                sess.seat = len(seats)
                seats.append(sess)
            if sess.tail_len:
                self._tails = _seat_write_jit(
                    self._tails, sess.tails, np.int32(sess.seat))
                written += 1
            sess.tails = None
        for hole in holes:              # those no joiner took, lowest first
            while seats[-1] is None:
                seats.pop()
            if hole >= len(seats):
                break                   # it was at the end, and is gone
            last = seats.pop()
            if last.tail_len:
                self._tails = _seat_move_jit(
                    self._tails, np.int32(last.seat), np.int32(hole))
                written += 1
            moved.append((last.seat, hole))
            last.seat = hole
            seats[hole] = last
        if rebuilt:
            written = len(batch)        # every seat was placed anew
        self.stats.note_tails(kept=len(batch) - written, written=written)
        return joined, moved

    def _seat_carries(self, joined: list[_Session], moved: list) -> None:
        """A family with a carry: bring the carry stack to the seating
        :meth:`_seat_batch` just made. A joiner's carry is written into
        its seat whatever it holds (a seat keeps what its last session
        left there), a moved seat's carry moves with it, and a session
        that kept its seat costs nothing."""
        for sess in joined:
            self._carry = _seat_write_jit(
                self._carry, sess.carry, np.int32(sess.seat))
            sess.carry = None
        for src, dst in moved:
            self._carry = _seat_move_jit(
                self._carry, np.int32(src), np.int32(dst))
        written = len(joined) + len(moved)
        self.stats.note_carry(kept=len(self._seats) - written,
                              written=written)

    def _unseat(self, sess: _Session) -> None:
        """Vacate the seat of a session that lives on. It takes its tail
        with it: one read of the seat, or fresh zeros when the tail is
        empty. (One that is over just stands up: :meth:`_finish`.)"""
        seat, sess.seat = sess.seat, None
        self._seats[seat] = None
        if self._has_carry:
            sess.carry = _seat_read_jit(self._carry, np.int32(seat))
        if sess.tail_len:
            sess.tails = _seat_read_jit(self._tails, np.int32(seat))
        else:
            sess.reset_tail()

    def _new_tails(self, b_pad: int) -> None:
        """Replace the tail stack by zeros of ``b_pad`` seats, all vacant:
        the first step, and a change of the padded batch size. As in
        :meth:`_warm_pool`, no program may be built when a seat first
        changes hands, at whatever tick that is: the three seat programs
        run once here on scratch zeros of this width and of the next one
        up (the widest is ``max_batch``'s)."""
        dt = jnp.dtype(self.cfg.dtype)
        fam = self.family

        def zeros(b: int) -> tuple:
            return tuple(
                jnp.zeros(shape, dt)
                for shape in fam.leaf_shapes(self.cfg, self.page_tokens, b))

        def carry_zeros(b: int) -> tuple:
            return _zero_carry(fam, self.cfg, b)

        stacks = (zeros, carry_zeros) if self._has_carry else (zeros,)
        self._tails = self._carry = None
        for b in {b_pad, min(2 * b_pad, _pow2(self.max_batch))}:
            if b not in self._seat_ready:
                at = np.int32(0)
                for make in stacks:
                    scratch = _seat_write_jit(make(b), make(1), at)
                    _seat_read_jit(_seat_move_jit(scratch, at, at), at)
                self._seat_ready.add(b)
        self._tails = zeros(b_pad)
        self._carry = carry_zeros(b_pad)
        self._seats = []

    def _tail(self, sess: _Session) -> tuple:
        """The session's tail, one (L, 1, KV, P, Hd) array a leaf: its
        own, or one read of its seat."""
        if sess.tails is not None:
            return sess.tails
        return _seat_read_jit(self._tails, np.int32(sess.seat))

    def _ship(self, sess: _Session) -> None:
        """Page boundary: the full tail becomes a stored page, one a
        kind of the family's page — the pending CoW clone when one is
        open, a published shared extent for prompt-only pages, a private
        page otherwise."""
        tail = self._tail(sess)
        prompt_only = sess.pos <= len(sess.prompt)
        pending = next((e for e in sess.entries if e.pending_fill), None)
        # The pages go to the store where they lie, on the device: one
        # sited in HOT never passes through the host, and the ship does
        # not wait for the program that made the tail.
        packed = _pack_pages_jit(
            tuple(tail[leaves] for leaves in self._kind_leaves),
            self.store_dtype)
        for k, leaves in enumerate(self._kind_leaves):
            arrays = tail[leaves]
            if pending is not None:
                self.store.write_page(pending.page, packed[k])
                entry = pending
                entry.pending_fill = False
            else:
                page = self.store.alloc_page(packed[k])
                entry = _Entry(page=page, kind=k)
                sess.entries.append(entry)
            # The tail just packed is the page's arrays, bit for bit what
            # a rebuild from the stored bytes gives (the store's dtype is
            # at least as wide as the model's).
            entry.arrays = arrays
            entry.version = entry.page.version
            if self.kinds[k].window is not None:
                self.stats.note_window(shipped=1)
        # With a prefix cache the page has one kind: `entry` is the page.
        if (self.prefix is not None and prompt_only and sess.chain_valid
                and not entry.page.shared):
            ext = self.prefix.publish(
                sess.chain_parent, tuple(sess.page_toks), entry.page,
                self._snapshot(sess) if self._has_carry else None,
            )
            if ext.page is entry.page:
                entry.extent = ext
                self._shared[ext.page.page_id] = entry
            else:
                # Dedup: another session published these tokens first and
                # its page won (this one is freed). The loser takes the
                # winner's entry, so a (page_id, version) has one bit
                # pattern, the stored one, in every context and pool row.
                sess.entries[-1] = self._entry_of(ext)
            self.prefix.acquire(ext)
            sess.shared_refs.append(ext)
            sess.chain_parent = ext
        elif not prompt_only:
            sess.chain_valid = False  # generated content: never publish
        if sess.seat is None:
            sess.reset_tail()
        else:
            # The seat stays the session's, and no zeros are made: the
            # fused step reads a row that enters with tail_len 0 as zeros.
            sess.tail_len = 0
            sess.page_toks = []

    def _drop_passed(self, sess: _Session) -> None:
        """After a ship: drop the pages of a kind with a window whose
        every key lies outside the window of every later query (a page
        that starts at ``s`` once ``s + P <= pos - window``, the rule of
        ``kv_paging.BucketedPagedDecoder``). A dropped page is freed in the
        store, whatever tier it lies in, its arrays are released and its
        pool row is free again: it is never demoted, nothing will read it.
        A session so lists ``ceil(window / P)`` pages of the kind at
        most."""
        P = self.page_tokens
        passed = []
        for k, kind in enumerate(self.kinds):
            if kind.window is None:
                continue
            while (sess.dropped[k] + 1) * P <= sess.pos - kind.window:
                first = next(e for e in sess.entries if e.kind == k)
                sess.entries.remove(first)
                slot = self._pool_slots[k].pop(
                    (first.page.page_id, first.version), None)
                if slot is not None:
                    self._pool_free[k].append(slot)
                first.arrays = None
                passed.append(first.page)
                sess.dropped[k] += 1
        if passed:
            # Freed here, not at the tick's end: HOT's occupancy, and every
            # placement decision with it, stays what it was.
            self.store.free_pages(passed)
            self.stats.note_window(dropped=len(passed))

    def _publishes_partial(self, sess: _Session, ahead: int = 0) -> bool:
        """Whether the prompt's end, ``ahead`` tokens from here, leaves a
        partial tail for :meth:`_publish_partial` to publish."""
        return (self.share_partials and self.prefix is not None
                and sess.chain_valid
                and 0 < sess.tail_len + ahead < self.page_tokens
                and sess.pos + ahead <= len(sess.prompt))

    def _publish_partial(self, sess: _Session) -> None:
        """End of prefill mid-page: publish the prompt's partial tail as
        a shareable extent (retention-only — this session's own copy
        stays in its tail buffers), with the carry snapshot taken before
        its last token where the family has a carry."""
        boundary, sess.boundary = sess.boundary, None
        if not self._publishes_partial(sess):
            if boundary is not None:
                self.store.free_page(boundary)
            return
        prompt_toks = sess.page_toks[:sess.tail_len]
        packed = jnp.stack(list(self._tail(sess))).astype(
            jnp.dtype(self.store_dtype)
        )
        raw = np.asarray(to_bytes(packed))
        page = self.store.alloc_page(raw)
        self.prefix.publish(sess.chain_parent, tuple(prompt_toks), page,
                            boundary)

    def _finish(self, sess: _Session, abandon: bool = False) -> None:
        """Stand a session up. The pages that were its own wait in
        ``_ended`` for :meth:`_free_ended`, which frees them with those of
        whoever else ended in the tick."""
        for ext in sess.shared_refs:
            self.prefix.release(ext)
        sess.shared_refs = []
        self._ended += [
            e.page for e in sess.entries
            if e.extent is None and not e.page.shared and not e.page.freed]
        sess.entries = []
        if sess.seat is not None:
            self._seats[sess.seat] = None
            sess.seat = None
        if not abandon:
            self.results.append(SessionResult(
                tenant=sess.req.tenant,
                prompt_len=len(sess.prompt),
                out_tokens=list(sess.out),
                stall_s=round(sess.stall_s, 6),
                prefix_tokens_reused=sess.prefix_tokens_reused,
                out_logits=list(sess.logits) if self.keep_logits else None,
                ttft_parts=sess.ttft_parts,
            ))

    def _free_ended(self) -> None:
        """The pages of every session that ended since the last call leave
        the store together: one scrub dispatch a group, the books once."""
        ended, self._ended = self._ended, []
        self.store.free_pages(ended)

    # -- introspection ----------------------------------------------------

    def _note_pages(self) -> None:
        """Look at the expert counts of the chunks since the last look: a
        host sync, made where the host waits for the device anyway."""
        if self._pages_touched:
            counts = jax.device_get(self._pages_touched)
            self.stats.note_moe_page(int(sum(counts)), len(counts))
            self._pages_touched = []

    def metrics_meta(self) -> dict:
        self._note_pages()
        meta = self.stats.snapshot()
        meta["prefetch"]["mode"] = self.prefetcher.mode
        if self.prefix is not None:
            meta["prefix"]["shared_bytes_live"] = self.prefix.shared_bytes()
        meta["cold_sim"] = self.store.cold_sim
        return meta
