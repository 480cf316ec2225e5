"""Cross-tenant prefix-cache sharing: a content-hash radix over KV pages.

The millions-of-users win (ROADMAP item 1): identical prompt prefixes
across tenants dedup into **shared read-only refcounted extents** — one
KV page computed and stored once, attended to by every tenant whose
prompt starts the same way. The structure is a radix trie at page
granularity: each node covers exactly one page of token ids (the last
node of a published prompt may be *partial* — fewer than ``page_tokens``
tokens), children are keyed by their token chunk, and every node carries
a chain content hash (SHA-1 over the parent's hash + this node's token
bytes) so an extent's identity is the *content of the whole prefix*,
never a tenant or session id.

Sharing rules (the vLLM/Mooncake discipline on OCM pages):

- an extent's page is marked ``shared``; while ``refs > 0`` it is
  immutable (``TieredPageStore.write_page`` refuses) and unevictable
  (``_victims`` skips it);
- a tenant that must append into a *partial* shared extent copies first
  (:meth:`TieredPageStore.cow`) — copy-on-write on divergence; the
  shared original survives byte-exact for everyone else;
- ``refs == 0`` extents stay cached (retention is the point of a prefix
  cache) until :meth:`sweep` reclaims unreferenced leaves under store
  pressure.

A family whose layers keep a recurrent carry beside their pages
(``models/kv_paging.py::PagedFamily.carry_leaves``) can resume from a
prefix only with the carry as it stood there, so each of its extents holds
a **snapshot** of it beside the page (:attr:`SharedExtent.carry`): a page of
the same store in a slot of its own, shared, referenced, counted, evicted,
deduplicated, swept and persisted with the extent's page.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

from oncilla_tpu.core.errors import OcmError
from oncilla_tpu.obs import journal as obs_journal
from oncilla_tpu.serving.metrics import ServingStats
from oncilla_tpu.serving.tiers import Page, TieredPageStore
from oncilla_tpu.utils.debug import printd


def _chain_hash(parent_key: str, tokens: tuple[int, ...]) -> str:
    h = hashlib.sha1(parent_key.encode("ascii"))
    h.update(b"\x00".join(str(t).encode("ascii") for t in tokens))
    return h.hexdigest()


@dataclass
class SharedExtent:
    """One radix node: a page of KV for one page of prefix tokens."""

    key: str
    tokens: tuple[int, ...]
    page: Page
    parent: "SharedExtent | None" = None
    children: dict = field(default_factory=dict)   # full-page nodes
    partials: dict = field(default_factory=dict)   # partial-tail nodes
    #: A family with a carry: the publisher's carry where an adopter of
    #: this extent resumes, as a page of the store. That is after the
    #: extent's last token for a full page (the adopter goes on with the
    #: next page) and before it for a partial tail (the adopter computes
    #: the prompt's last token itself, for its logits).
    carry: Page | None = None

    @property
    def fill(self) -> int:
        return len(self.tokens)

    @property
    def nbytes(self) -> int:
        """The extent's bytes in the store: its page and its snapshot."""
        return self.page.nbytes + (self.carry.nbytes if self.carry else 0)

    @property
    def refs(self) -> int:
        return self.page.refs


class PrefixCache:
    """The page-granular radix trie over one :class:`TieredPageStore`."""

    def __init__(self, store: TieredPageStore, page_tokens: int,
                 stats: ServingStats | None = None):
        self.store = store
        self.page_tokens = int(page_tokens)
        self.stats = stats or store.stats
        #: Bytes of the carry snapshot every extent holds (the engine of a
        #: family with a carry sets it; 0: no extent holds one). What
        #: :meth:`restore` holds a persisted extent to.
        self.carry_nbytes = 0
        self._root = SharedExtent(key="", tokens=(), page=None)  # sentinel

    # -- lookup -----------------------------------------------------------

    def match(self, tokens) -> tuple[list[SharedExtent], int]:
        """Longest shared prefix of ``tokens``: full-page extents chunk
        by chunk, then (when what remains is a short tail) an exact
        partial extent. Returns (extents, tokens_matched); the caller
        must :meth:`acquire` before using any page."""
        toks = tuple(int(t) for t in tokens)
        node = self._root
        matched: list[SharedExtent] = []
        i = 0
        P = self.page_tokens
        while i + P <= len(toks):
            child = node.children.get(toks[i:i + P])
            if child is None:
                break
            matched.append(child)
            node = child
            i += P
        rest = toks[i:]
        if 0 < len(rest) < P:
            part = node.partials.get(rest)
            if part is not None:
                matched.append(part)
                i += len(rest)
        return matched, i

    def child(self, parent: SharedExtent | None, tokens) -> SharedExtent | None:
        """The single extent extending ``parent`` by exactly ``tokens``
        (full-page or partial by length) — the incremental form of
        :meth:`match`, what the engine probes at every page boundary so
        prompts arriving *simultaneously* still dedup: session B adopts
        the page session A published one turn earlier."""
        node = parent or self._root
        toks = tuple(int(t) for t in tokens)
        table = (node.children if len(toks) == self.page_tokens
                 else node.partials)
        return table.get(toks)

    # -- publication ------------------------------------------------------

    def publish(self, parent: SharedExtent | None, tokens, page: Page,
                carry: Page | None = None) -> SharedExtent:
        """Publish ``page`` as the KV for ``tokens`` extending
        ``parent`` (None = the prompt's first page), with the carry
        snapshot ``carry`` of a family that keeps one. Content-hash
        dedup: when the chain already carries this exact extent —
        another tenant prefilled the same prefix first — the fresh page
        and its snapshot are returned to the store and the existing
        extent wins, so the cache can never hold two copies of one
        prefix."""
        node = parent or self._root
        toks = tuple(int(t) for t in tokens)
        if not 0 < len(toks) <= self.page_tokens:
            raise ValueError(f"extent of {len(toks)} tokens "
                             f"(page is {self.page_tokens})")
        table = (node.children if len(toks) == self.page_tokens
                 else node.partials)
        existing = table.get(toks)
        if existing is not None:
            self.store.free_pages(
                [p for p, kept in ((page, existing.page),
                                   (carry, existing.carry))
                 if p is not None and p is not kept])
            return existing
        page.shared = True
        if carry is not None:
            carry.shared = True
            self.stats.note_carry_bytes(carry.nbytes)
        ext = SharedExtent(
            key=_chain_hash(node.key, toks), tokens=toks, page=page,
            parent=None if node is self._root else node, carry=carry,
        )
        table[toks] = ext
        self.stats.note_extents(+1)
        obs_journal.record("prefix_publish", key=ext.key[:12],
                           tokens=len(toks), nbytes=page.nbytes,
                           partial=len(toks) < self.page_tokens)
        return ext

    # -- refcounts --------------------------------------------------------

    def acquire(self, ext: SharedExtent) -> None:
        ext.page.refs += 1
        if ext.carry is not None:
            ext.carry.refs += 1
        self.stats.note_prefix_hit(ext.nbytes)
        obs_journal.record("prefix_hit", key=ext.key[:12],
                           refs=ext.page.refs, nbytes=ext.nbytes)

    def release(self, ext: SharedExtent) -> None:
        if ext.page.refs <= 0:
            raise ValueError(f"release of unreferenced extent {ext.key[:12]}")
        ext.page.refs -= 1
        if ext.carry is not None:
            ext.carry.refs -= 1
        self.stats.note_prefix_release(ext.nbytes)

    # -- retention --------------------------------------------------------

    def _walk(self, node: SharedExtent):
        for table in (node.children, node.partials):
            for ext in table.values():
                yield ext
                yield from self._walk(ext)

    def extents(self) -> list[SharedExtent]:
        return list(self._walk(self._root))

    def shared_bytes(self) -> int:
        """Bytes deduplicated: each extra reference beyond the first is
        a page, and with it a carry snapshot, some tenant did NOT have to
        store privately."""
        return sum(max(e.page.refs - 1, 0) * e.nbytes
                   for e in self.extents())

    # -- persistence (FROZEN tier, ROADMAP item 5) ------------------------

    def persist(self, frozen) -> int:
        """Write every extent's page bytes (its carry snapshot's behind
        them, in the one entry: a page never comes back without it) + trie
        position into a :class:`~oncilla_tpu.persist.FrozenStore`
        (``prefix-<chainhash>`` keys). Parent-first (:meth:`_walk` order)
        so a restored store is always a valid trie prefix even if the
        write is cut short. Returns the number of extents persisted."""
        n = 0
        live = {f"prefix-{ext.key}" for ext in self.extents()}
        for fkey in frozen.keys():
            # The store is an exact manifest of the trie: a chain swept
            # since the last persist must not resurrect at restore.
            if fkey.startswith("prefix-") and fkey not in live:
                frozen.delete(fkey)
        for ext in self.extents():
            data = self.store.read_page(ext.page).tobytes()
            if ext.carry is not None:
                data += self.store.read_page(ext.carry).tobytes()
            frozen.write(
                f"prefix-{ext.key}",
                data,
                meta={
                    "kind": "prefix",
                    "key": ext.key,
                    "tokens": list(ext.tokens),
                    "parent": ext.parent.key if ext.parent else "",
                    "nbytes": int(ext.page.nbytes),
                    "carry_nbytes": int(ext.carry.nbytes) if ext.carry else 0,
                },
            )
            n += 1
        obs_journal.record("prefix_persist", extents=n)
        return n

    def restore(self, frozen) -> int:
        """Re-publish persisted extents from ``frozen`` into the trie —
        the warm-boot leg. Parents restore before children (chain-hash
        identity demands it); a chain with a missing or corrupt ancestor
        is dropped WHOLE below the break (a child must never publish over
        a hole — its chain hash would lie about the bytes beneath it), and
        so is one whose carry snapshot is not of :attr:`carry_nbytes` bytes
        (persisted without one for a family that needs it, or the reverse).
        Returns the number of extents re-published."""
        import numpy as np

        recs: dict[str, tuple[str, dict]] = {}
        for fkey in frozen.keys():
            if not fkey.startswith("prefix-"):
                continue
            meta = frozen.meta(fkey)
            if meta.get("kind") == "prefix":
                recs[meta["key"]] = (fkey, meta)

        def depth(key: str) -> int | None:
            d = 0
            while key:
                rec = recs.get(key)
                if rec is None:
                    return None  # broken ancestry: skip the whole chain
                key = rec[1]["parent"]
                d += 1
            return d

        published: dict[str, SharedExtent | None] = {"": None}
        n = 0
        order = sorted(
            (k for k in recs if depth(k) is not None),
            key=lambda k: depth(k),
        )
        for key in order:
            fkey, meta = recs[key]
            parent_key = meta["parent"]
            if parent_key not in published:
                continue  # parent refused at read time below
            if int(meta.get("carry_nbytes", 0)) != self.carry_nbytes:
                printd("prefix restore: dropping chain at %s (its carry "
                       "snapshot is %s B, this family's %d B)", fkey,
                       meta.get("carry_nbytes", 0), self.carry_nbytes)
                continue
            try:
                data = frozen.read_bytes(fkey)
            except OcmError:
                # Typed refusal (corrupt entry quarantined by the store):
                # this chain ends here — descendants stay unpublished.
                printd("prefix restore: dropping chain at %s "
                       "(frozen entry refused)", fkey)
                continue
            raw = np.frombuffer(data, dtype=np.uint8)
            cut = len(raw) - self.carry_nbytes
            page = self.store.alloc_page(raw[:cut], shared=True)
            carry = (self.store.alloc_page(raw[cut:], shared=True)
                     if self.carry_nbytes else None)
            ext = self.publish(
                published[parent_key], tuple(meta["tokens"]), page, carry
            )
            published[key] = ext
            n += 1
        obs_journal.record("prefix_restore", extents=n,
                           persisted=len(recs))
        return n

    def sweep(self) -> int:
        """Reclaim unreferenced LEAF extents (children first — an inner
        node's page may still back a referenced chain below it).
        Returns the number of pages freed."""
        freed = []
        changed = True
        while changed:
            changed = False
            for node in [self._root, *self.extents()]:
                for table in (node.children, node.partials):
                    for toks, ext in list(table.items()):
                        if (ext.page.refs == 0 and not ext.children
                                and not ext.partials):
                            del table[toks]
                            ext.page.shared = False
                            freed.append(ext.page)
                            if ext.carry is not None:
                                ext.carry.shared = False
                                freed.append(ext.carry)
                                self.stats.note_carry_bytes(
                                    -ext.carry.nbytes)
                            self.stats.note_extents(-1)
                            changed = True
        self.store.free_pages(freed)
        return len(freed)
