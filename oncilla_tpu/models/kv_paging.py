"""KV-cache paging through OCM handles: long-context decode whose KV pages
live anywhere in the pod — local HBM, a *remote* chip's HBM (ICI fabric), or
remote host DRAM (DCN fabric) — BASELINE.md config 5.

The decode working set stays small: a local tail window of the KV cache plus
a list of opaque OCM handles for completed pages. Attention over the full
context fetches pages back through the data plane. This is exactly the
reference's usage pattern (allocate remote, fill with ocm put, read back
with ocm get — test/ocm_test.c test 2) with a transformer as the
application.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.core.handle import OcmAlloc
from oncilla_tpu.core.hbm import from_bytes, to_bytes
from oncilla_tpu.core.kinds import OcmKind
from oncilla_tpu.models.llama import LlamaConfig
from oncilla_tpu.utils.debug import GLOBAL_TRACER


@dataclass(frozen=True)
class PageKind:
    """One kind of page of a family: ``layers`` layers cache their
    positions in it (the ``L`` of its ``n_leaves`` leaves), and a query
    looks back over ``window`` positions of them (None: over all). A page
    of a kind with a window is of no use once every key in it lies outside
    the window of every later query; the engine then drops it."""

    layers: int
    window: int | None = None
    n_leaves: int = 1


@dataclass(frozen=True)
class PagedFamily:
    """What :class:`~oncilla_tpu.serving.engine.ServingEngine` takes from a
    model family: the leaves a page and a tail are made of, and the
    programs it dispatches over them. A page is ``n_leaves`` arrays of ONE
    shape ``(L, 1, KV, P, Hd)`` with ``(KV, Hd) = leaf_dims(cfg)`` (the
    dense family: K and V; the latent family: one latent of KV 1); a tail
    is the same with the batch on axis 1, a pool row the same without it.
    The tier store sees a page's bytes and nothing of this.

    ``step(params, tokens, meta, n_real, pool, table, tails, cfg)`` is the
    fused batch step and ``page(params, tokens_page, meta, ctx, tails,
    cfg)`` the page program; ``pool``, ``ctx`` and ``tails`` are tuples of
    leaves, both return ``(logits, new tails, aux)``. ``aux`` is None, or
    for a family with experts a () int32 on the device: the distinct
    (layer, expert) pairs that received a real token. ``write_row(pool,
    page, slot)`` writes one page into a pool row in place; the engine
    does not call it, not even as the group of one: it brings its pool up
    to a batch with two programs of its own, the same for every family
    (``serving.engine._pool_write_jit`` writes several pages a dispatch,
    ``_pool_gather_jit`` carries the rows over a change of capacity).
    The benchmark's warmers call it, a kind at a time.
    ``assignments_per_token(cfg)``, for a family whose programs hand an
    ``aux`` back, is how many (layer, expert) pairs one token is routed
    to.

    Five things are the family's to say, and most say none.
    ``cached_layers(cfg)`` is how many layers keep pages (the ``L`` of a
    leaf; absent: ``cfg.n_layers``). ``carry_leaves(cfg, batch)`` gives a
    family whose layers keep a recurrent state of fixed size beside (or
    in place of) pages its **carry**: the ``(shape, dtype)`` of each leaf,
    the batch on axis 1 as in a tail. Such a family's ``step`` and
    ``page`` take the carry as one more argument after ``cfg``, donated,
    and return it as a fourth value; the engine keeps the seated
    sessions' carries in one stack, as it keeps their tails.

    ``kinds(cfg)`` gives a family whose layers do not all cache alike its
    page's **kinds** (:class:`PageKind`), each with its own layer count,
    leaves and lifetime: a session then ships one page a kind at every
    page boundary, ``n_leaves`` counts the leaves of all kinds, and
    ``pool``, ``ctx`` and ``tails`` hold them kind by kind. ``table`` is a
    tuple of block tables, one a kind, and ``meta`` has a context length
    and a first position a kind: a step's rows are ``[pos, tail_len,
    ctx_len, ctx_start, ctx_len, ctx_start, ...]``, a page's ``[pos0,
    ctx_start, ctx_start, ...]``. A family that says nothing has one kind
    of every cached layer, its ``table`` is the one array and its ``meta``
    what it has always been.

    ``context(pages, cfg, page_tokens)`` gives a family its own way of
    joining a session's pages into the page program's ``ctx``: ``pages``
    is, a kind, the list of that kind's pages in context order, each a
    tuple of leaves. Absent: every leaf's pages concatenated along the
    token axis, one small program a context length and leaf shape.

    ``chunk_pages`` is the most whole pages of one prompt the ``page``
    program takes in one dispatch (absent: 1). A family that states more,
    which has no carry, also takes ``tokens_page`` of ``chunk_pages`` pages
    and the count of the real ones as the keyword ``pages`` (a () int32;
    the rows after them are padding), and returns the logits of each
    page's last position and a tuple of leaves for each page, in order,
    where it returns the new tails. Called without ``pages`` it is the
    program of one page."""

    n_leaves: int
    leaf_dims: object
    step: object
    page: object
    write_row: object = None
    assignments_per_token: object = None
    cached_layers: object = None
    carry_leaves: object = None
    kinds: object = None
    context: object = None
    chunk_pages: int = 1

    def page_kinds(self, cfg) -> tuple:
        if self.kinds is not None:
            return tuple(self.kinds(cfg))
        layers = (self.cached_layers(cfg) if self.cached_layers
                  else cfg.n_layers)
        return (PageKind(layers, None, self.n_leaves),)

    def kind_leaves(self, cfg) -> list:
        """Where each kind's leaves lie among the family's: a slice a
        kind."""
        ends = np.cumsum([k.n_leaves for k in self.page_kinds(cfg)]).tolist()
        return [slice(a, b) for a, b in zip([0] + ends, ends)]

    def leaf_shapes(self, cfg, page_tokens: int, batch: int = 1) -> tuple:
        """The shape of every leaf, kind by kind."""
        kv, hd = self.leaf_dims(cfg)
        return tuple((kind.layers, batch, kv, page_tokens, hd)
                     for kind in self.page_kinds(cfg)
                     for _ in range(kind.n_leaves))

    def leaf_shape(self, cfg, page_tokens: int, batch: int = 1) -> tuple:
        """The one shape of a family that names no kinds."""
        return self.leaf_shapes(cfg, page_tokens, batch)[0]


@dataclass
class PagedKVCache:
    """KV pages for one decode session.

    ``backend`` is anything with alloc/free/put/get — an :class:`Ocm`
    context (local arms) or a :class:`ControlPlaneClient` (remote arms).
    Page layout: both K and V of one page are packed into a single
    allocation: (2, L, B, KV, page_tokens, Hd) bitcast to bytes.
    """

    backend: object
    cfg: LlamaConfig
    batch: int
    page_tokens: int = 128
    kind: OcmKind = OcmKind.REMOTE_DEVICE
    dtype: str = "float32"
    pages: list[OcmAlloc] = field(default_factory=list)
    # Registered receive buffer for host-kind fetches (PR-3 get(out=)):
    # grown geometrically, reused across fetch_pages calls so the remote
    # tier never allocates a fresh destination per fetch (a fresh array
    # costs a page fault per 4 KiB — at GB scale most of the transfer).
    _recvbuf: np.ndarray | None = field(
        default=None, repr=False, compare=False
    )

    @property
    def page_shape(self) -> tuple:
        c = self.cfg
        return (2, c.n_layers, self.batch, c.n_kv_heads, self.page_tokens,
                c.head_dim)

    @property
    def page_bytes(self) -> int:
        return int(np.prod(self.page_shape)) * jnp.dtype(self.dtype).itemsize

    @property
    def tokens_paged(self) -> int:
        return len(self.pages) * self.page_tokens

    def store_page(self, k_page: jax.Array, v_page: jax.Array) -> OcmAlloc:
        """Ship one completed page into the pod (one-sided put). k/v:
        (L, B, KV, page_tokens, Hd)."""
        packed = jnp.stack([k_page, v_page]).astype(jnp.dtype(self.dtype))
        assert packed.shape == self.page_shape, (packed.shape, self.page_shape)
        with GLOBAL_TRACER.span("kv_store_page", nbytes=self.page_bytes):
            h = self.backend.alloc(self.page_bytes, self.kind)
            self.backend.put(h, to_bytes(packed), 0)
        self.pages.append(h)
        return h

    def _recv_slots(self, npages: int) -> np.ndarray | None:
        """The registered receive window for ``npages`` host-kind
        fetches: one reusable buffer, one page-sized slot per page
        (distinct regions, so slot i stays valid while slot i+1 lands).
        None for device kinds — their gets stay device-resident."""
        if self.kind not in (OcmKind.REMOTE_HOST, OcmKind.LOCAL_HOST):
            return None
        need = self.page_bytes * npages
        if self._recvbuf is None or self._recvbuf.nbytes < need:
            # Geometric growth: a steadily lengthening decode re-registers
            # O(log pages) times, not per page boundary.
            cap = max(need, 2 * (self._recvbuf.nbytes if self._recvbuf
                                 is not None else self.page_bytes))
            self._recvbuf = np.empty(cap, dtype=np.uint8)
        return self._recvbuf

    def _fetch_one(self, h: OcmAlloc, out: np.ndarray | None):
        """One page's raw bytes — through the registered-receive path
        (``get(out=)`` / ``get_into``) when ``out`` is given."""
        if out is None:
            return self.backend.get(h, self.page_bytes, 0)
        get = self.backend.get
        try:
            return get(h, self.page_bytes, 0, out=out)
        except TypeError:
            pass  # backend without an out= kwarg (e.g. a raw client)
        get_into = getattr(self.backend, "get_into", None)
        if get_into is not None:
            return get_into(h, out, 0)
        out[:] = np.asarray(get(h, self.page_bytes, 0)).view(
            np.uint8).reshape(-1)
        return out

    def fetch_pages(self) -> tuple[jax.Array, jax.Array] | None:
        """Gather every page back (one-sided gets) and concatenate along the
        token axis: (L, B, KV, tokens_paged, Hd) x2. Host-kind pages land
        in the cache's registered receive buffer (PR-3 ``get(out=)``)
        instead of a fresh destination per fetch."""
        if not self.pages:
            return None
        ks, vs = [], []
        slots = self._recv_slots(len(self.pages))
        nb = self.page_bytes
        with GLOBAL_TRACER.span(
            "kv_fetch_pages", nbytes=self.page_bytes * len(self.pages)
        ):
            for i, h in enumerate(self.pages):
                out = slots[i * nb:(i + 1) * nb] if slots is not None else None
                raw = self._fetch_one(h, out)
                # jnp.asarray: device-resident gets stay on device (a
                # numpy round-trip here would cost a sync + two transfers
                # per page); host-arm gets upload once.
                packed = from_bytes(
                    jnp.asarray(raw), self.page_shape, self.dtype
                )
                ks.append(packed[0])
                vs.append(packed[1])
        return jnp.concatenate(ks, axis=3), jnp.concatenate(vs, axis=3)

    def drop_oldest(self) -> None:
        """Free the oldest page (sliding-window eviction).

        The caller MUST track the global position of the first retained
        page and feed it to the decode step (``ctx_start`` in
        :func:`paged_decode_step_jit`, as :class:`BucketedPagedDecoder`
        does) — after an eviction, retained pages no longer start at
        absolute position 0, and a decoder that assumes they do
        (:class:`PagedDecoder` / :func:`paged_decode_step`) would
        attribute wrong positions to every key."""
        self.backend.free(self.pages.pop(0))

    def free(self) -> None:
        for h in self.pages:
            self.backend.free(h)
        self.pages.clear()


def paged_decode_step(
    params: dict,
    token: jax.Array,
    pos: int,
    k_ctx: jax.Array | None,
    v_ctx: jax.Array | None,
    cfg: LlamaConfig,
    layer_params_fn=None,
    mlp_of=None,
):
    """Decode one token attending over the full valid context.

    k_ctx/v_ctx: (L, B, KV, T, Hd) — paged pages + local tail concatenated,
    containing exactly the T = ``pos`` valid entries (no masking needed);
    None when pos == 0. Returns (logits, (new_k, new_v)) where new_k/new_v
    are this token's (L, B, KV, 1, Hd) cache entries.

    Reuses :func:`llama.block` — one transformer-block implementation for
    training, cached decode, and paged decode. ``layer_params_fn``/
    ``mlp_of`` are the family hooks (see ``llama.decode_step``): the MoE
    family passes its slicer + expert-FFN factory and pages its KV the
    same way.
    """
    from oncilla_tpu.models import llama

    lp_fn = layer_params_fn or llama.layer_params
    x = params["embed"][token][:, None, :].astype(jnp.dtype(cfg.dtype))
    positions = jnp.asarray([pos])
    new_k, new_v = [], []

    for i in range(cfg.n_layers):
        def attend(q, kn, vn, i=i):
            new_k.append(kn)
            new_v.append(vn)
            if k_ctx is not None:
                k_all = jnp.concatenate(
                    [k_ctx[i].astype(q.dtype), kn.astype(q.dtype)], axis=2
                )
                v_all = jnp.concatenate(
                    [v_ctx[i].astype(q.dtype), vn.astype(q.dtype)], axis=2
                )
            else:
                k_all, v_all = kn.astype(q.dtype), vn.astype(q.dtype)
            mask = None
            if cfg.window is not None:
                # Keys are laid out by absolute position 0..pos.
                mask = (jnp.arange(k_all.shape[2]) > pos - cfg.window)[None, :]
            return llama.grouped_attention(q, k_all, v_all, mask)

        lp = lp_fn(params, i)
        x = llama.block(cfg, x, lp, positions, attend,
                        mlp=mlp_of(lp) if mlp_of else None)

    logits = llama.final_logits(params, x, cfg)
    return logits[:, 0], (jnp.stack(new_k), jnp.stack(new_v))


@partial(
    jax.jit,
    static_argnames=("cfg", "layer_params_fn", "mlp_of"),
    donate_argnums=(5, 6),
)
def paged_decode_step_jit(
    params: dict,
    token: jax.Array,      # (B,) current token ids
    meta: jax.Array,       # (3,) int32 [pos, tail_len, ctx_start]
    k_ctx: jax.Array,      # (L, B, KV, C, Hd) paged context; C may be 0
    v_ctx: jax.Array,
    tail_k: jax.Array,     # (L, B, KV, P, Hd) local tail buffer (donated)
    tail_v: jax.Array,
    cfg: LlamaConfig,
    layer_params_fn=None,
    mlp_of=None,
):
    """Shape-bucketed jitted paged decode.

    Unlike :func:`paged_decode_step` (whose context length grows by one
    every token, forcing an XLA recompile per step), the tail lives in a
    fixed (L, B, KV, P, Hd) buffer masked by ``tail_len``, so the traced
    shapes change only when the paged context ``C`` grows by a page:
    O(tokens / page_tokens) compilations instead of O(tokens). This is the
    static-shape formulation TPU/XLA wants and what makes paged decode
    usable as a real-chip benchmark (BASELINE.md config 5).

    Per-step host traffic is ONE packed (3,) int32 transfer: ``meta``
    carries [pos, tail_len, ctx_start] (ctx_start = global position of
    ``k_ctx[..., 0, :]`` after evictions), instead of three separate
    scalar uploads. The tail buffers are donated: XLA updates them in
    place instead of allocating fresh ones per step.

    Returns (logits, new_tail_k, new_tail_v); the caller owns tail_len
    bookkeeping and page shipping. ``layer_params_fn``/``mlp_of`` are the
    family hooks (static under jit) — see :func:`paged_decode_step`.
    """
    from oncilla_tpu.models import llama

    lp_fn = layer_params_fn or llama.layer_params
    return _paged_token(
        params, token, meta[0], meta[1], meta[2], k_ctx, v_ctx,
        tail_k, tail_v, cfg, lp_fn, mlp_of,
    )


def _paged_token(params, token, pos, tail_len, ctx_start, k_ctx, v_ctx,
                 tail_k, tail_v, cfg, lp_fn, mlp_of):
    """One paged-decode token: the traced body shared by the per-token jit
    (:func:`paged_decode_step_jit`) and the sampling scan
    (:func:`paged_generate_page_jit`: sampling is sequential by nature).
    :func:`paged_decode_page_jit`, whose tokens are all known, applies the
    same mask rule to a whole page at once. All of pos/tail_len/ctx_start
    are traced scalars."""
    from oncilla_tpu.models import llama

    x = params["embed"][token][:, None, :].astype(jnp.dtype(cfg.dtype))
    positions = pos[None]
    P = tail_k.shape[3]
    C = k_ctx.shape[3]
    # Keys = [paged context (all valid) | tail slots (valid through this
    # step's insertion at index tail_len)].
    valid = jnp.concatenate(
        [jnp.ones((C,), bool), jnp.arange(P) <= tail_len]
    )[None, :]
    if cfg.window is not None:
        # Global key positions: paged context starts at ctx_start (pages
        # before it may have been evicted), tail slot j holds position
        # pos - tail_len + j; band-limit to the query's last `window`.
        gk = jnp.concatenate(
            [ctx_start + jnp.arange(C), (pos - tail_len) + jnp.arange(P)]
        )
        valid &= (gk > pos - cfg.window)[None, :]

    for i in range(cfg.n_layers):
        state = {}

        def attend(q, kn, vn, i=i, state=state):
            tk = jax.lax.dynamic_update_slice(
                tail_k[i], kn.astype(tail_k.dtype), (0, 0, tail_len, 0)
            )
            tv = jax.lax.dynamic_update_slice(
                tail_v[i], vn.astype(tail_v.dtype), (0, 0, tail_len, 0)
            )
            state["tk"], state["tv"] = tk, tv
            k_all = jnp.concatenate(
                [k_ctx[i].astype(q.dtype), tk.astype(q.dtype)], axis=2
            )
            v_all = jnp.concatenate(
                [v_ctx[i].astype(q.dtype), tv.astype(q.dtype)], axis=2
            )
            return llama.grouped_attention(q, k_all, v_all, valid)

        lp = lp_fn(params, i)
        x = llama.block(cfg, x, lp, positions, attend,
                        mlp=mlp_of(lp) if mlp_of else None)
        tail_k = tail_k.at[i].set(state["tk"])
        tail_v = tail_v.at[i].set(state["tv"])

    logits = llama.final_logits(params, x, cfg)
    return logits[:, 0], tail_k, tail_v


@partial(
    jax.jit,
    static_argnames=("cfg", "layer_params_fn", "mlp_of"),
    donate_argnums=(6, 7),
)
def paged_decode_batch_step_jit(
    params: dict,
    tokens: jax.Array,     # (B,) current token ids, one per session
    meta: jax.Array,       # (B, 4) int32 [pos, tail_len, ctx_len, ctx_start]
    pool_k: jax.Array,     # (N, L, KV, P, Hd) resident page pool
    pool_v: jax.Array,
    table: jax.Array,      # (B, MP) int32 pool row per context page
    tail_k: jax.Array,     # (L, B, KV, P, Hd) per-session tails (donated)
    tail_v: jax.Array,
    cfg: LlamaConfig,
    layer_params_fn=None,
    mlp_of=None,
):
    """ONE fused decode step for a whole batch of paged sessions — the
    true-batched serving formulation (ROADMAP item 1): instead of one
    batch-of-1 :func:`paged_decode_step_jit` dispatch per session per
    step, every runnable session advances one token in a single compiled
    program.

    The paged context rides a **block table**: ``pool_k``/``pool_v``
    stack every distinct resident page ONCE (a prefix page shared by k
    sessions occupies one pool row, not k copies), and ``table[b]``
    lists session *b*'s pages in context order, 0-padded past its
    ``ctx_len``/page count. The gather (``pool[table]``) happens inside
    the jit, so the host hands over O(B·MP) int32 indices per step, not
    O(B·C·model) floats.

    Per-session ``meta`` rows carry [pos, tail_len, ctx_len, ctx_start]:
    validity is masked per row (padded context slots and empty tail
    slots attend to nothing), positions/rope are per row, and the tail
    insertion scatters each session's new K/V at its own ``tail_len``.
    A row whose ``tail_len`` is 0 reads its tail as zeros whatever the
    buffer holds, so the caller may leave a shipped page where it lies.
    Sessions shorter than the padded shapes see extra masked keys whose
    softmax weight is exactly 0, so padding changes no sum's terms. On
    the CPU backend in float32 the emitted logits are bitwise those of
    the batch-of-1 step (the paired byte-exact gate leans on this); an
    accelerator may tile a matmul differently per batch shape, and there
    agreement is checked at logit level against the unpaged forward
    (``chip_smoke.py``).

    Callers bucket B, MP and N to powers of two so compilations stay
    O(log batch · log pages), never O(tokens) (the
    :class:`~oncilla_tpu.serving.engine.ServingEngine` policy).
    Returns (logits (B, vocab), new_tail_k, new_tail_v).
    """
    from oncilla_tpu.models import llama

    lp_fn = layer_params_fn or llama.layer_params
    pos, tail_len = meta[:, 0], meta[:, 1]
    ctx_len, ctx_start = meta[:, 2], meta[:, 3]
    P = tail_k.shape[3]
    B = tokens.shape[0]
    MP = table.shape[1]
    C = MP * P

    # (B, MP) rows -> (L, B, KV, C, Hd) gathered context. Padded table
    # slots gather pool row 0; they are masked out below via ctx_len.
    gk = jnp.take(pool_k, table, axis=0)  # (B, MP, L, KV, P, Hd)
    gv = jnp.take(pool_v, table, axis=0)
    k_ctx = gk.transpose(2, 0, 3, 1, 4, 5).reshape(
        pool_k.shape[1], B, pool_k.shape[2], C, pool_k.shape[4]
    )
    v_ctx = gv.transpose(2, 0, 3, 1, 4, 5).reshape(
        pool_v.shape[1], B, pool_v.shape[2], C, pool_v.shape[4]
    )

    x = params["embed"][tokens][:, None, :].astype(jnp.dtype(cfg.dtype))
    positions = pos[:, None]  # (B, 1): per-session rope
    valid = jnp.concatenate(
        [
            jnp.arange(C)[None, :] < ctx_len[:, None],
            jnp.arange(P)[None, :] <= tail_len[:, None],
        ],
        axis=1,
    )  # (B, C + P)
    if cfg.window is not None:
        gpos = jnp.concatenate(
            [
                ctx_start[:, None] + jnp.arange(C)[None, :],
                (pos - tail_len)[:, None] + jnp.arange(P)[None, :],
            ],
            axis=1,
        )
        valid &= gpos > (pos[:, None] - cfg.window)
    mask = valid[:, None, :]  # (B, Sq=1, C+P)
    # Per-session tail insertion at each row's own tail_len (the batched
    # twin of the step path's dynamic_update_slice).
    slot = jnp.arange(P)[None, :] == tail_len[:, None]  # (B, P)
    slot4 = slot[:, None, :, None]
    # A row that enters with tail_len 0 starts a page: whatever its seat
    # of the tail stack still holds (the page its session shipped, another
    # session's tail) reads as zeros, so a page is zeros beyond its fill
    # without anybody writing them.
    live4 = (tail_len > 0)[:, None, None, None]

    for i in range(cfg.n_layers):
        state = {}

        def attend(q, kn, vn, i=i, state=state):
            tk = jnp.where(slot4, kn.astype(tail_k.dtype),
                           jnp.where(live4, tail_k[i], 0))
            tv = jnp.where(slot4, vn.astype(tail_v.dtype),
                           jnp.where(live4, tail_v[i], 0))
            state["tk"], state["tv"] = tk, tv
            k_all = jnp.concatenate(
                [k_ctx[i].astype(q.dtype), tk.astype(q.dtype)], axis=2
            )
            v_all = jnp.concatenate(
                [v_ctx[i].astype(q.dtype), tv.astype(q.dtype)], axis=2
            )
            return llama.grouped_attention(q, k_all, v_all, mask)

        lp = lp_fn(params, i)
        x = llama.block(cfg, x, lp, positions, attend,
                        mlp=mlp_of(lp) if mlp_of else None)
        tail_k = tail_k.at[i].set(state["tk"])
        tail_v = tail_v.at[i].set(state["tv"])

    logits = llama.final_logits(params, x, cfg)
    return logits[:, 0], tail_k, tail_v


@partial(jax.jit, donate_argnums=(0, 1))
def paged_pool_write_row_jit(
    pool_k: jax.Array,     # (N, L, KV, P, Hd) resident page pool (donated)
    pool_v: jax.Array,
    page_k: jax.Array,     # (L, 1, KV, P, Hd) one page's decode arrays
    page_v: jax.Array,
    slot: jax.Array,       # () int32 pool row to overwrite
):
    """Write one page into row ``slot`` of the fused step's page pool, in
    place: both pools are donated and ``slot`` is traced, so one compiled
    program per pool shape serves every row. A row is a byte copy of
    the page (``dynamic_update_slice``), so a pool kept up to date this
    way is bitwise the pool stacked from the same pages. The engine writes
    its pool several pages a dispatch with a program of its own
    (``serving.engine._pool_write_jit``, the same update a page); this one
    stays behind ``PagedFamily.write_row`` for the benchmark's warmers."""
    at = (slot, 0, 0, 0, 0)
    return (
        jax.lax.dynamic_update_slice(pool_k, page_k[None, :, 0], at),
        jax.lax.dynamic_update_slice(pool_v, page_v[None, :, 0], at),
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "layer_params_fn", "mlp_of"),
    donate_argnums=(5, 6),
)
def paged_decode_page_jit(
    params: dict,
    tokens_page: jax.Array,  # (B, P) one full page of token ids
    meta: jax.Array,         # (2,) int32 [pos0, ctx_start]
    k_ctx: jax.Array,        # (L, B, KV, C, Hd) paged context; C may be 0
    v_ctx: jax.Array,
    tail_k: jax.Array,       # (L, B, KV, P, Hd) tail buffer (donated)
    tail_v: jax.Array,
    cfg: LlamaConfig,
    layer_params_fn=None,
    mlp_of=None,
):
    """One full page of paged decode as ONE compiled program that takes
    the page's P tokens through each layer TOGETHER, so every weight is
    read once a page (a per-token loop streams the whole model once a
    token): one :func:`llama.block` a layer over ``(B, P, D)``, attention
    over the paged OCM context and, causally, the page's own keys, and the
    LM head once at the end.

    Starts from an empty tail (whatever the donated buffers hold is
    overwritten); token j of the page sits at absolute position pos0 + j
    and attends to every context key and to the page's keys 0..j, band-
    limited to its last ``cfg.window`` positions where that is set: the
    rule :func:`_paged_token` applies a token at a time. The page's fresh
    K/V are rounded through the tail's dtype before they are attended to,
    so a later step that reads the shipped page sees the values this
    program saw. Returns (logits (B, P, vocab), new_tail_k, new_tail_v),
    the tails full: the caller ships them as a page.

    ``mlp_of`` sees the page's P tokens at once: under a capacity-dropping
    expert dispatch (:mod:`oncilla_tpu.models.moe`) the page's tokens are
    routed together and the capacity is reckoned over all of them, as in
    training, not a token at a time.
    """
    from oncilla_tpu.models import llama

    lp_fn = layer_params_fn or llama.layer_params
    pos0, ctx_start = meta[0], meta[1]
    P = tail_k.shape[3]
    C = k_ctx.shape[3]
    x = params["embed"][tokens_page].astype(jnp.dtype(cfg.dtype))
    positions = pos0 + jnp.arange(P)
    # Keys = [paged context (all valid) | the page's own (causal)].
    valid = jnp.concatenate(
        [jnp.ones((P, C), bool), jnp.tril(jnp.ones((P, P), bool))], axis=1
    )
    if cfg.window is not None:
        # Global key positions, as in _paged_token: the context starts at
        # ctx_start, the page at pos0; each query keeps its last `window`.
        gk = jnp.concatenate([ctx_start + jnp.arange(C), positions])
        valid &= gk[None, :] > (positions - cfg.window)[:, None]

    for i in range(cfg.n_layers):
        state = {}

        def attend(q, kn, vn, i=i, state=state):
            tk, tv = kn.astype(tail_k.dtype), vn.astype(tail_v.dtype)
            state["tk"], state["tv"] = tk, tv
            k_all = jnp.concatenate(
                [k_ctx[i].astype(q.dtype), tk.astype(q.dtype)], axis=2
            )
            v_all = jnp.concatenate(
                [v_ctx[i].astype(q.dtype), tv.astype(q.dtype)], axis=2
            )
            return llama.grouped_attention(q, k_all, v_all, valid)

        lp = lp_fn(params, i)
        x = llama.block(cfg, x, lp, positions, attend,
                        mlp=mlp_of(lp) if mlp_of else None)
        tail_k = tail_k.at[i].set(state["tk"])
        tail_v = tail_v.at[i].set(state["tv"])

    return llama.final_logits(params, x, cfg), tail_k, tail_v


@partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "layer_params_fn", "mlp_of"),
    donate_argnums=(5, 6),
)
def paged_generate_page_jit(
    params: dict,
    token0: jax.Array,       # (B,) the token that seeds this page
    meta: jax.Array,         # (2,) int32 [pos0, ctx_start]
    k_ctx: jax.Array,
    v_ctx: jax.Array,
    tail_k: jax.Array,       # (L, B, KV, P, Hd) empty tail (donated)
    tail_v: jax.Array,
    cfg: LlamaConfig,
    key: jax.Array,
    temperature: float = 0.0,
    layer_params_fn=None,
    mlp_of=None,
):
    """One page of *autoregressive* paged decode as ONE compiled program:
    each scan tick consumes the previous tick's sample (greedy at
    ``temperature`` 0, else softmax sampling), so unlike the teacher-forced
    :func:`paged_decode_page_jit` it is a ``lax.scan`` of
    :func:`_paged_token` that streams the weights once a token: the
    per-page serving loop proper (the paged counterpart of
    :func:`llama.generate`'s sampling scan).

    Returns (sampled ids (B, P), new_tail_k, new_tail_v). The tail holds
    K/V of every *consumed* token this page (token0 + the first P-1
    samples); the final sample is output-only and seeds the next page.
    """
    from oncilla_tpu.models import llama

    lp_fn = layer_params_fn or llama.layer_params
    pos0, ctx_start = meta[0], meta[1]
    P = tail_k.shape[3]

    def pick(logits_b, k):
        return llama.sample_token(logits_b, k, temperature, token0.dtype)

    def body(carry, inp):
        tok, tail_k, tail_v = carry
        j, k_j = inp
        logits, tail_k, tail_v = _paged_token(
            params, tok, pos0 + j, j, ctx_start, k_ctx, v_ctx,
            tail_k, tail_v, cfg, lp_fn, mlp_of,
        )
        nxt = pick(logits, k_j)
        return (nxt, tail_k, tail_v), nxt

    keys = jax.random.split(key, P)
    (last, tail_k, tail_v), out = jax.lax.scan(
        body, (token0, tail_k, tail_v), (jnp.arange(P), keys)
    )
    return out.transpose(1, 0), tail_k, tail_v


class BucketedPagedDecoder:
    """Jitted decode session with OCM-paged KV history.

    Same contract as :class:`PagedDecoder`, but decode steps run through
    :func:`paged_decode_step_jit` with a fixed-size masked tail, so a long
    decode compiles once per *page* rather than once per *token*.
    """

    def __init__(
        self,
        params: dict,
        cfg: LlamaConfig,
        backend,
        batch: int = 1,
        page_tokens: int = 16,
        kind: OcmKind = OcmKind.REMOTE_DEVICE,
        dtype: str = "float32",
        refetch: bool = False,
        layer_params_fn=None,
        mlp_of=None,
    ):
        """``refetch=True`` re-reads the *whole* paged context through the
        OCM data plane (one-sided gets) at every page boundary instead of
        extending a locally retained copy — O(pages^2) read traffic, the
        mode that actually exercises the get path (and what a resumed
        session with no local copy would do every page)."""
        self.params = params
        self.cfg = cfg
        self.cache = PagedKVCache(backend, cfg, batch, page_tokens, kind, dtype)
        self.page_tokens = page_tokens
        self.refetch = refetch
        self._hooks = dict(layer_params_fn=layer_params_fn, mlp_of=mlp_of)
        self.pos = 0
        self._ctx_start = 0  # global position of the first retained page
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, page_tokens, cfg.head_dim)
        dt = jnp.dtype(cfg.dtype)
        self._tail_k = jnp.zeros(shape, dt)
        self._tail_v = jnp.zeros(shape, dt)
        self._tail_len = 0
        # Paged context starts empty (C = 0); grows a page at a time.
        empty = shape[:3] + (0,) + shape[4:]
        self._fetched = (jnp.zeros(empty, dt), jnp.zeros(empty, dt))

    def step(self, token: jax.Array) -> jax.Array:
        meta = jnp.asarray(
            [self.pos, self._tail_len, self._ctx_start], dtype=jnp.int32
        )
        logits, self._tail_k, self._tail_v = paged_decode_step_jit(
            self.params, token, meta,
            self._fetched[0], self._fetched[1],
            self._tail_k, self._tail_v, self.cfg,
            **self._hooks,
        )
        self.pos += 1
        self._tail_len += 1
        if self._tail_len == self.page_tokens:
            self._ship_page()
        return logits

    def step_page(self, tokens_page: jax.Array) -> jax.Array:
        """Take one FULL page of teacher-forced tokens through every layer
        together in a single compiled dispatch
        (:func:`paged_decode_page_jit`: the weights are read once a page),
        then ship the page: a prompt's prefill, a page at a time. Requires
        an empty tail (step/step_page calls must align to page boundaries)
        and ``tokens_page.shape[-1] == page_tokens``. Returns per-token
        logits (B, P, vocab)."""
        if self._tail_len != 0:
            raise ValueError(
                f"step_page needs an empty tail (tail_len="
                f"{self._tail_len}); align step()/step_page() calls to "
                "page boundaries"
            )
        if tokens_page.shape[-1] != self.page_tokens:
            raise ValueError(
                f"step_page wants exactly page_tokens="
                f"{self.page_tokens} ids, got {tokens_page.shape[-1]}"
            )
        meta = jnp.asarray([self.pos, self._ctx_start], dtype=jnp.int32)
        logits, self._tail_k, self._tail_v = paged_decode_page_jit(
            self.params, tokens_page, meta,
            self._fetched[0], self._fetched[1],
            self._tail_k, self._tail_v, self.cfg,
            **self._hooks,
        )
        self.pos += self.page_tokens
        self._tail_len = self.page_tokens
        self._ship_page()
        return logits

    def generate_page(self, token: jax.Array, *, key: jax.Array | None = None,
                      temperature: float = 0.0) -> jax.Array:
        """Autoregressively sample one full page in a single compiled
        dispatch (:func:`paged_generate_page_jit`), then ship it. ``token``
        is the (B,) seed (the previous page's last sample, or the last
        prompt token); returns the (B, page_tokens) sampled ids — the last
        of which seeds the next ``generate_page`` call. Greedy at
        ``temperature`` 0, else softmax sampling with ``key``. Requires an
        empty tail (page-boundary-aligned, same as :meth:`step_page`)."""
        if self._tail_len != 0:
            raise ValueError(
                f"generate_page needs an empty tail (tail_len="
                f"{self._tail_len}); align calls to page boundaries"
            )
        if key is None:
            key = jax.random.key(self.pos)
        meta = jnp.asarray([self.pos, self._ctx_start], dtype=jnp.int32)
        out, self._tail_k, self._tail_v = paged_generate_page_jit(
            self.params, token, meta,
            self._fetched[0], self._fetched[1],
            self._tail_k, self._tail_v, self.cfg, key,
            temperature=temperature,
            **self._hooks,
        )
        self.pos += self.page_tokens
        self._tail_len = self.page_tokens
        self._ship_page()
        return out

    def _ship_page(self) -> None:
        """Page boundary: ship the full tail into the pod and extend the
        local concat (same O(pages) traffic policy as PagedDecoder.step);
        with ``refetch`` re-read the whole paged context instead."""
        k_page = self._tail_k.astype(jnp.dtype(self.cache.dtype))
        v_page = self._tail_v.astype(jnp.dtype(self.cache.dtype))
        self.cache.store_page(k_page, v_page)
        dt = jnp.dtype(self.cfg.dtype)
        # Sliding-window eviction: a page whose every key is outside
        # the window of all future queries (>= self.pos) is freed from
        # OCM and dropped from the local concat, keeping the working
        # set O(window) instead of O(pos) — the rolling-buffer
        # semantics of the Mistral scheme, on paged storage.
        if self.cfg.window is not None:
            while (self.cache.pages and self._ctx_start
                   + self.page_tokens <= self.pos - self.cfg.window):
                self.cache.drop_oldest()
                self._ctx_start += self.page_tokens
                if not self.refetch:
                    self._fetched = (
                        self._fetched[0][:, :, :, self.page_tokens:],
                        self._fetched[1][:, :, :, self.page_tokens:],
                    )
        if self.refetch:
            fk, fv = self.cache.fetch_pages()
            self._fetched = (fk.astype(dt), fv.astype(dt))
        else:
            self._fetched = (
                jnp.concatenate(
                    [self._fetched[0], k_page.astype(dt)], axis=3
                ),
                jnp.concatenate(
                    [self._fetched[1], v_page.astype(dt)], axis=3
                ),
            )
        # Stale tail contents are masked out by tail_len; no need to
        # zero the buffers.
        self._tail_len = 0

    def close(self) -> None:
        self.cache.free()


class PagedDecoder:
    """A decode session whose KV history pages out through OCM.

    The local working set is one page of tail KV; every ``page_tokens``
    steps the tail ships into the pod (remote chip HBM / remote host DRAM
    per ``kind``) and decode continues against fetched pages + fresh tail —
    the Llama-KV-cache-in-remote-pod-HBM loop of BASELINE.md config 5.
    """

    def __init__(
        self,
        params: dict,
        cfg: LlamaConfig,
        backend,
        batch: int = 1,
        page_tokens: int = 16,
        kind: OcmKind = OcmKind.REMOTE_DEVICE,
        dtype: str = "float32",
        layer_params_fn=None,
        mlp_of=None,
    ):
        self.params = params
        self.cfg = cfg
        self.cache = PagedKVCache(
            backend, cfg, batch, page_tokens, kind, dtype
        )
        self.page_tokens = page_tokens
        self._hooks = dict(layer_params_fn=layer_params_fn, mlp_of=mlp_of)
        self.pos = 0
        self._tail_k: list = []  # per-step (L, B, KV, 1, Hd)
        self._tail_v: list = []
        self._fetched = None  # concatenated paged context (k, v)

    def _context(self):
        parts_k, parts_v = [], []
        if self.cache.pages:
            if self._fetched is None:
                # Cold start (e.g. resuming a session): one bulk fetch.
                self._fetched = self.cache.fetch_pages()
            parts_k.append(self._fetched[0])
            parts_v.append(self._fetched[1])
        if self._tail_k:
            parts_k.append(jnp.concatenate(self._tail_k, axis=3))
            parts_v.append(jnp.concatenate(self._tail_v, axis=3))
        if not parts_k:
            return None, None
        return (
            jnp.concatenate(parts_k, axis=3),
            jnp.concatenate(parts_v, axis=3),
        )

    def step(self, token: jax.Array) -> jax.Array:
        k_ctx, v_ctx = self._context()
        logits, (nk, nv) = paged_decode_step(
            self.params, token, self.pos, k_ctx, v_ctx, self.cfg,
            **self._hooks,
        )
        self._tail_k.append(nk)
        self._tail_v.append(nv)
        self.pos += 1
        if len(self._tail_k) == self.page_tokens:
            # Ship the full tail into the pod; extend the local fetched
            # concat with the page we already hold instead of refetching
            # every page (keeps remote traffic O(pages), not O(pages^2)).
            k_page = jnp.concatenate(self._tail_k, axis=3).astype(
                jnp.dtype(self.cache.dtype)
            )
            v_page = jnp.concatenate(self._tail_v, axis=3).astype(
                jnp.dtype(self.cache.dtype)
            )
            self.cache.store_page(k_page, v_page)
            if self._fetched is None and len(self.cache.pages) > 1:
                self._fetched = self.cache.fetch_pages()
            elif self._fetched is None:
                self._fetched = (k_page, v_page)
            else:
                self._fetched = (
                    jnp.concatenate([self._fetched[0], k_page], axis=3),
                    jnp.concatenate([self._fetched[1], v_page], axis=3),
                )
            self._tail_k, self._tail_v = [], []
        return logits

    def close(self) -> None:
        self.cache.free()
