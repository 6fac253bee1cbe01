"""Paged KV cache: fixed-size pages + per-sequence block tables.

The bucketed engine's dense cache couples cache capacity to the *batch*:
every row owns ``[S_max]`` slots whether it uses 7 of them or 120.  The
paged cache decouples the two (the vLLM/TensorRT-LLM in-flight-batching
layout): one physical pool of ``n_pages`` pages of ``page_size`` tokens
each, and per-sequence **block tables** mapping logical block ``t //
page_size`` to a physical page.  Mixed-length sequences then share one
jitted decode step — the step's shapes depend only on ``(max_seqs,
max_blocks, page_size)``, never on any prompt length — and a finished
row's pages return to the pool immediately.

Layout per attention layer: each page is stored flat, as ``bs·F``
contiguous values (``page_size`` token rows of ``F`` features each), with
a leading ``n_periods`` dim added by the scan stacking:

  * ``attn``: ``k_pages`` / ``v_pages``  ``[n_periods, n_pages, bs·Hk·D]``
  * ``mla``:  ``ckv_pages`` ``[n_periods, n_pages, bs·r]``,
              ``krope_pages`` ``[n_periods, n_pages, bs·dr]``
  * both:     ``block_table`` ``[n_periods, max_seqs, max_blocks]`` int32,
              ``lengths`` ``[n_periods, max_seqs]`` int32

The flat minor dim is a multiple of 128 at real widths, so the TPU
compiler tiles the pool without padding and keeps it row-major: token
``t`` of a page is the ``F`` values at ``(t % bs)·F``.  :func:`page_view`
and :func:`flat_pages` turn a page into its ``[bs, *feat]`` token rows
and back; every helper here goes through them or writes rows at that
offset.  The layer loop carries the whole stacked pool and each layer
writes and gathers at ``(layer, page)`` in place, so no step slices out
or copies a layer's pool (models/transformer.py ``apply_blocks``).

Physical page 0 is the **trash page**: the block-table entries of empty
slots (and of logical blocks past a sequence's end) point at it, so every
gather/scatter stays in bounds with no per-row branching — reads through
it are masked by ``lengths`` and writes to it are discarded garbage.

The device-side helpers here (:func:`gather_pages`, the ``write_*``
helpers, :func:`copy_pages`) are pure jnp and are consumed by
``models/attention.py``; the host-side :class:`PageAllocator` free list
is consumed by ``serve/scheduler.py``.
"""

from __future__ import annotations

import collections
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TRASH_PAGE",
    "PagedCacheConfig",
    "PageAllocator",
    "PrefixCache",
    "make_paged_cache",
    "page_feat",
    "page_view",
    "flat_pages",
    "set_tables",
    "gather_pages",
    "write_token",
    "write_token_window",
    "write_packed_tokens",
    "write_prompt_pages",
    "copy_pages",
]

#: physical page reserved as the write-target / read-source of inactive
#: rows; never handed out by the allocator, never read unmasked.
TRASH_PAGE = 0


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Static shape of the paged pool.

    ``max_blocks * page_size`` is the per-sequence capacity (the paged
    analogue of the dense cache's ``S_max``); ``n_pages`` bounds the
    *total* tokens resident across all sequences — the knob that trades
    memory for concurrency.  Page 0 is reserved (trash), so the usable
    pool is ``n_pages - 1`` pages.

    ``resident_blocks`` (optional) caps how many of a sequence's blocks
    are ever physically resident at once: sliding-window serving evicts
    pages behind the window, so a pool far smaller than ``max_blocks``
    can still serve arbitrarily long rows.  It only relaxes the
    feasibility check here — block tables keep ``max_blocks`` columns
    (positions stay absolute; evicted entries point at trash).
    """

    page_size: int = 16
    n_pages: int = 129          # 128 usable + trash
    max_seqs: int = 8           # decode slots (R)
    max_blocks: int = 8         # logical blocks per sequence
    resident_blocks: int | None = None   # physical bound (None = max_blocks)

    def __post_init__(self):
        if self.page_size < 1 or self.n_pages < 2:
            raise ValueError("need page_size >= 1 and n_pages >= 2")
        need = self.max_blocks if self.resident_blocks is None \
            else min(self.max_blocks, self.resident_blocks)
        if self.n_pages - 1 < need:
            raise ValueError(
                f"pool of {self.n_pages - 1} usable pages cannot hold even "
                f"one resident sequence ({need} blocks)")

    @property
    def tokens_per_seq(self) -> int:
        return self.page_size * self.max_blocks


class PageAllocator:
    """Refcounted host-side free list over pages 1..n_pages-1 (0 = trash).

    Prefix caching maps several sequences' block tables (plus the prefix
    index itself) onto one physical page, so every allocated page carries
    a reference count: :meth:`alloc` hands out pages at refcount 1,
    :meth:`incref` registers another holder, and :meth:`free` releases
    ONE holder's reference — the page returns to the free list only when
    the last holder lets go.

    ``free`` stays IDEMPOTENT for fully-released pages: a page already on
    the free list is skipped rather than raised on.  The scheduler can
    preempt a sequence in the same engine step that it finishes (growth
    runs before the finished check), and the preemption path and the
    completion path both release pages — releasing twice must not corrupt
    the free list, hand one physical page to two sequences, or drive a
    *shared* page's count below its other holders' (the scheduler zeroes
    a stale state's page list at its first release, so a double release
    can only ever see an already-free page).  Out-of-range ids still
    raise: those are real bugs, not benign races.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        # LIFO reuse keeps the working set of hot pages small
        self._free = list(range(n_pages - 1, TRASH_PAGE, -1))
        self._free_set = set(self._free)    # O(1) idempotence check
        self._ref: dict[int, int] = {}      # allocated page -> #holders
        # cumulative traffic counters (the zero-redundant-write assertions
        # in tests/benchmarks read these)
        self.pages_allocated = 0            # fresh pages handed out
        self.pages_shared = 0               # increfs (block-table reuse)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def utilization(self) -> float:
        usable = self.n_pages - 1
        return (usable - len(self._free)) / max(usable, 1)

    def refcount(self, pg: int) -> int:
        """Current holder count (0 for free pages)."""
        return self._ref.get(pg, 0)

    def alloc(self, n: int) -> list[int] | None:
        """Pop ``n`` pages at refcount 1, or None (no change) if short."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        for pg in pages:
            self._ref[pg] = 1
        self.pages_allocated += n
        return pages

    def incref(self, pages: list[int]) -> None:
        """Register another holder of already-allocated pages."""
        for pg in pages:
            if self._ref.get(pg, 0) < 1:
                raise ValueError(f"incref of unallocated page {pg}")
            self._ref[pg] += 1
        self.pages_shared += len(pages)

    def free(self, pages: list[int]) -> None:
        for pg in pages:
            if not (TRASH_PAGE < pg < self.n_pages):
                raise ValueError(f"bad page id {pg}")
            if pg in self._free_set:
                continue                    # already free: idempotent
            self._ref[pg] -= 1
            if self._ref[pg] > 0:
                continue                    # other holders keep it alive
            del self._ref[pg]
            self._free.append(pg)
            self._free_set.add(pg)


class PrefixCache:
    """Content-addressed prefix -> physical-page index (host side).

    Causal attention makes a page's KV a pure function of the token
    prefix ending at that page, so a page can be keyed by the *exact
    bytes* of that prefix: ``key(i) = tokens[: (i+1)*page_size]`` for a
    full block, ``key = tokens[:T]`` for a prompt's partial last block.
    Exact byte keys mean lookups can never alias distinct prefixes — two
    different prefixes have different keys, full stop (no hashing
    collisions to reason about; python's dict hashing is an
    implementation detail behind exact key equality).

    Entries hold one allocator reference each (the index is a holder like
    any sequence), so a cached page survives its producer and is
    reclaimed by :meth:`evict` (LRU) when the pool runs dry.  Partial
    entries expose ``valid`` tokens; an adopting sequence reads only
    positions < ``valid`` (masked by its lengths) and COW-splits the page
    on its first write into it (see serve/scheduler.py).

    **Liveness guard.**  Every hit is re-validated against the allocator
    before it is returned: an entry whose page shows refcount 0 is STALE
    — some holder over-released and the page went back to the pool (from
    where it may be handed to an unrelated row and rewritten) while the
    index still pointed at it.  Returning it to a byte-identical resubmit
    would silently serve foreign KV.  Stale entries are dropped on sight
    (:meth:`lookup`/:meth:`_get`, :meth:`evict`), skipped by
    :meth:`peek_cached_tokens`, and refused by :meth:`insert` (a trash or
    unallocated page is never indexed); ``stale_drops`` counts the
    self-heals so tests can assert the guard fired.
    """

    def __init__(self, alloc: PageAllocator, page_size: int):
        self.alloc = alloc
        self.bs = page_size
        # key -> (page, valid_tokens); ordered = LRU (oldest first)
        self._entries: collections.OrderedDict[bytes, tuple[int, int]] = (
            collections.OrderedDict())
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.stale_drops = 0    # entries dropped by the liveness guard
        #: bumped whenever the entry set changes — peek results are only
        #: valid within one generation (the scheduler memoizes on it)
        self.generation = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _bytes(tokens: np.ndarray, n: int) -> bytes:
        return np.ascontiguousarray(tokens[:n], np.int32).tobytes()

    def _get(self, key: bytes):
        e = self._entries.get(key)
        if e is None:
            return None
        if self.alloc.refcount(e[0]) < 1:
            # stale: the page was over-released back to the pool while
            # the index held it — drop the entry so a byte-identical
            # resubmit misses cleanly instead of adopting a page that
            # may since have been reallocated and rewritten
            del self._entries[key]
            self.stale_drops += 1
            self.generation += 1
            return None
        self._entries.move_to_end(key)      # LRU touch
        return e

    def lookup(self, tokens: np.ndarray):
        """Per-block share map for a prompt: ([page_or_None per block],
        n_cached_tokens).  Blocks are independent — the key of block i
        embeds the whole prefix, so a later block can hit even if an
        earlier one was evicted (the admitting sequence recomputes and
        blits the misses; the hits are adopted read-only).  The last
        partial block hits only on an exact whole-prompt match.  Pages
        are returned WITHOUT a reference; the adopter increfs.
        """
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        T = len(tokens)
        shared: list[int | None] = []
        n_cached = 0
        for i in range(T // self.bs):
            e = self._get(self._bytes(tokens, (i + 1) * self.bs))
            shared.append(e[0] if e is not None else None)
            if e is not None:
                n_cached += self.bs
                self.hits += 1
            else:
                self.misses += 1
        if T % self.bs:
            e = self._get(self._bytes(tokens, T))
            shared.append(e[0] if e is not None else None)
            if e is not None:
                n_cached += T % self.bs
                self.hits += 1
            else:
                self.misses += 1
        return shared, n_cached

    def peek_cached_tokens(self, tokens: np.ndarray) -> int:
        """Cached-token count for a prompt WITHOUT touching LRU order or
        the hit/miss counters — the scheduler's admission-preference scan
        probes every waiting request and must not pollute either."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        T = len(tokens)
        n = 0

        def live(key):      # liveness-checked, mutation-free probe
            e = self._entries.get(key)
            return e is not None and self.alloc.refcount(e[0]) >= 1

        for i in range(T // self.bs):
            if live(self._bytes(tokens, (i + 1) * self.bs)):
                n += self.bs
        if T % self.bs and live(self._bytes(tokens, T)):
            n += T % self.bs
        return n

    def insert(self, tokens: np.ndarray, pages: list[int]) -> int:
        """Register a prefilled prompt's blocks; returns #new entries.

        Every full block (and the partial tail, if any) is keyed by its
        prefix bytes and increfs its page.  Keys that already exist are
        left alone — by content addressing the existing page holds the
        identical KV.
        """
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        T = len(tokens)
        added = 0
        ends = [(i + 1) * self.bs for i in range(T // self.bs)]
        if T % self.bs:
            ends.append(T)
        for i, end in enumerate(ends):
            key = self._bytes(tokens, end)
            if key in self._entries or i >= len(pages):
                continue
            if pages[i] == TRASH_PAGE or self.alloc.refcount(pages[i]) < 1:
                continue    # evicted/placeholder block: never index it
            self.alloc.incref([pages[i]])
            self._entries[key] = (pages[i], end)
            self._entries.move_to_end(key)
            added += 1
        if added:
            self.generation += 1
        return added

    def evict(self, n_pages: int) -> int:
        """Release up to ``n_pages`` LRU entries whose page would
        actually return to the pool (refcount 1 — index-only holders);
        entries still shared by running sequences are kept (hot).
        Returns the number of pages freed."""
        freed = 0
        dropped = 0
        for key in list(self._entries):
            if freed >= n_pages:
                break
            page, _ = self._entries[key]
            rc = self.alloc.refcount(page)
            if rc == 0:                 # stale (over-released): self-heal
                del self._entries[key]
                self.stale_drops += 1
                dropped += 1
                continue
            if rc != 1:
                continue
            del self._entries[key]
            self.alloc.free([page])
            self.evictions += 1
            freed += 1
        if freed or dropped:
            self.generation += 1
        return freed


# ------------------------------------------------------- device pytrees ---
def page_feat(cfg, name: str) -> tuple[int, ...]:
    """Per-token feature shape of the pool ``name`` of a paged cache."""
    if name in ("k_pages", "v_pages"):
        return (cfg.n_kv_heads, cfg.d_head)
    if name == "ckv_pages":
        return (cfg.mla.kv_lora_rank,)
    if name == "krope_pages":
        return (cfg.mla.qk_rope_dim,)
    raise KeyError(name)


def page_view(pages, feat):
    """Flat pages ``[..., bs·F]`` as token rows ``[..., bs, *feat]``."""
    return pages.reshape(pages.shape[:-1] + (-1,) + tuple(feat))


def flat_pages(rows, feat):
    """Token rows ``[..., bs, *feat]`` as flat pages ``[..., bs·F]`` (the
    inverse of :func:`page_view`)."""
    return rows.reshape(rows.shape[:rows.ndim - len(feat) - 1] + (-1,))


def _layer_pages(cfg, lt: str, pcfg: PagedCacheConfig, dtype):
    if lt == "attn":
        names = ("k_pages", "v_pages")
    elif lt == "mla":
        names = ("ckv_pages", "krope_pages")
    else:
        raise NotImplementedError(
            f"paged serving supports attn/mla layers only, got {lt!r} "
            "(SSM states are fixed-size per sequence — nothing to page)")
    return {n: jnp.zeros((pcfg.n_pages, pcfg.page_size
                          * math.prod(page_feat(cfg, n))), dtype)
            for n in names}


def make_paged_cache(cfg, pcfg: PagedCacheConfig, *, dtype=jnp.bfloat16):
    """Zero paged decode cache, periods-stacked like ``model.make_cache``."""
    p = cfg.period
    n_periods = cfg.n_layers // p

    def one_period():
        per = {}
        for j in range(p):
            c = _layer_pages(cfg, cfg.layer_types[j], pcfg, dtype)
            c["block_table"] = jnp.zeros(
                (pcfg.max_seqs, pcfg.max_blocks), jnp.int32)
            c["lengths"] = jnp.zeros((pcfg.max_seqs,), jnp.int32)
            per[f"l{j}"] = c
        return per

    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape),
        one_period())


def set_tables(cache, block_tables, lengths):
    """Overwrite every layer's block table + lengths from host arrays.

    The scheduler owns both as numpy state; the engine pushes them into
    the device cache right before each decode step (tiny transfers — the
    page pool itself never leaves the device).
    """
    bt = jnp.asarray(block_tables, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)

    def walk(d):
        out = {}
        for k, v in d.items():
            if k == "block_table":
                out[k] = jnp.broadcast_to(bt[None], (v.shape[0],) + bt.shape)
            elif k == "lengths":
                out[k] = jnp.broadcast_to(ln[None], (v.shape[0],) + ln.shape)
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    return walk(cache)


# ------------------------------------------------------ gather / scatter --
# Every helper below takes the stacked pool ``[n_periods, P, bs·F]`` and
# the period index ``layer`` it reads or writes, so the layer loop can
# carry the pool and update it in place.

def gather_pages(pages, layer, block_table, feat):
    """Pool + [R, nb] table -> dense [R, nb*bs, *feat] view of ``layer``.

    Logical token position t of row r lives at token row ``t % bs`` of
    page ``block_table[r, t // bs]``; one gather at ``(layer,
    block_table)`` reads the rows' pages straight from the stacked pool
    and lays them out contiguously, so downstream attention is
    *identical* to the dense-cache path (bit for bit — asserted in
    tests/test_kv_cache.py).
    """
    R = block_table.shape[0]
    g = page_view(pages[layer, block_table], feat)    # [R, nb, bs, *feat]
    return g.reshape((R, -1) + tuple(feat))


def _write_rows(pages, layer, page, off, vals):
    """Write token row m of ``vals`` [M, *feat] at row ``off[m]`` of page
    ``page[m]`` of ``layer``: ``F`` contiguous values at ``off[m]·F``,
    one scatter into the pool, which XLA updates in place."""
    M = page.shape[0]
    rows = vals.reshape(M, -1).astype(pages.dtype)
    idx = jnp.stack([jnp.broadcast_to(layer, (M,)), page,
                     off * rows.shape[1]], axis=1).astype(jnp.int32)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 2))
    return jax.lax.scatter(pages, idx, rows, dnums,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _page_size(pages, feat):
    return pages.shape[-1] // math.prod(feat)


def write_token(pages, layer, block_table, lengths, vals):
    """Scatter one new token per row at its current length.

    ``vals`` [R, *feat]: row r goes to page ``block_table[r, lengths[r] //
    bs]`` offset ``lengths[r] % bs``.  Rows whose tables point at the
    trash page (inactive slots) write garbage there harmlessly — and a
    row somehow past capacity (block index >= nb) is *redirected* to the
    trash page rather than clipped onto a real page, so a scheduler bug
    can never corrupt a live token.
    """
    bs = _page_size(pages, vals.shape[1:])
    blk = lengths // bs
    page = jnp.take_along_axis(block_table, blk[:, None], axis=1,
                               mode="fill", fill_value=TRASH_PAGE)[:, 0]
    return _write_rows(pages, layer, page, lengths % bs, vals)


def write_token_window(pages, layer, block_table, lengths, vals):
    """Scatter W consecutive tokens per row starting at its length.

    ``vals`` [R, W, *feat] (a speculative-verify window): token i of row
    r goes to logical position ``lengths[r] + i``.  Like
    :func:`write_token`, positions past the row's table (block index >=
    nb) or on unallocated blocks redirect to the trash page, so draft
    tokens past a row's pages lose their KV harmlessly — the engine caps
    acceptance to what landed on real pages.
    """
    bs = _page_size(pages, vals.shape[2:])
    R, W = vals.shape[:2]
    pos = lengths[:, None] + jnp.arange(W)[None]            # [R, W]
    page = jnp.take_along_axis(block_table, pos // bs, axis=1,
                               mode="fill", fill_value=TRASH_PAGE)
    return _write_rows(pages, layer, page.reshape(-1),
                       (pos % bs).reshape(-1),
                       vals.reshape((R * W,) + vals.shape[2:]))


def write_packed_tokens(pages, layer, block_table, seg, pos, vals):
    """Scatter N packed tokens at explicit (segment, position) coords.

    ``vals`` [N, *feat] (a mixed chunked-prefill/decode step): token i
    goes to logical position ``pos[i]`` of row ``seg[i]`` — physical page
    ``block_table[seg[i], pos[i] // bs]`` offset ``pos[i] % bs``.  Unlike
    :func:`write_token`/:func:`write_token_window`, each token carries
    its own segment and position, so one scatter serves any mix of
    prefill chunks and decode rows.  Pad lanes carry ``seg = -1`` and
    are redirected to the trash page, as are positions past a row's
    table (block index >= nb) — invalid lanes lose their KV harmlessly.
    """
    bs = _page_size(pages, vals.shape[1:])
    R = block_table.shape[0]
    segc = jnp.clip(seg, 0, R - 1)
    page = jnp.take_along_axis(block_table[segc], (pos // bs)[:, None],
                               axis=1, mode="fill",
                               fill_value=TRASH_PAGE)[:, 0]
    page = jnp.where(seg >= 0, page, TRASH_PAGE)
    return _write_rows(pages, layer, page, pos % bs, vals)


def copy_pages(pages, src, dst):
    """Copy page contents src[i] -> dst[i] in every layer of the pool.

    ``pages`` [n_periods, P, bs·F]; ``src``/``dst`` [m] int32.  The
    copy-on-write split: a sequence about to write into a shared page
    first duplicates it onto a fresh page and repoints its block table.
    No-op rows pass ``src = dst = TRASH_PAGE`` (trash copies onto trash),
    which keeps the jitted copy's shapes fixed — one compile ever.
    """
    return pages.at[:, dst].set(pages[:, src])


def write_prompt_pages(pages, block_row, planes):
    """Blit one prefilled prompt into its pages in every layer.

    ``pages`` [n_periods, P, bs·F]; ``block_row`` [nbp] physical page per
    logical block (trash for blocks past the prompt); ``planes``
    [n_periods, 1, Tpad, *feat] with ``Tpad == nbp * bs``.  Whole pages
    are overwritten — positions past the prompt length hold garbage that
    ``lengths`` masks at read time.
    """
    npr, Tpad = planes.shape[0], planes.shape[2]
    nbp = block_row.shape[0]
    feat = planes.shape[3:]
    v = flat_pages(planes.reshape((npr, nbp, Tpad // nbp) + feat), feat)
    return pages.at[:, block_row].set(v.astype(pages.dtype))
