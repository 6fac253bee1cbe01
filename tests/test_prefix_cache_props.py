"""Property tests for the refcounted allocator + prefix cache invariants.

The four invariants the PR-5 sharing machinery stands on:
  * a page's refcount is never negative and never goes stale — free
    pages + referenced pages always partition the usable pool;
  * ``free`` is idempotent under sharing: once a page has fully returned
    to the pool, further frees are no-ops (and a shared page only drops
    ONE holder per free);
  * a COW split preserves the gathered KV of every OTHER holder
    bit-for-bit (the frozen original is untouched; the copy is exact);
  * prefix-hash lookup never aliases distinct prefixes — a hit on block
    ``b`` implies the querying prompt's prefix through block ``b`` is
    byte-identical to the registered one.

Runs under hypothesis when installed (the CI extra); collects and skips
cleanly without it (tests/_hypothesis_stub.py).
"""

import numpy as np
import pytest
from _hypothesis_stub import given, st

import jax.numpy as jnp

from repro.serve.kv_cache import (
    TRASH_PAGE,
    PageAllocator,
    PrefixCache,
    copy_pages,
    gather_pages,
)


# ---------------------------------------------------------- allocator -----
@given(st.lists(st.tuples(st.sampled_from(["alloc", "incref", "free"]),
                          st.integers(min_value=1, max_value=4)),
                max_size=60))
def test_allocator_refcount_invariants(ops):
    """Random alloc/incref/free interleavings against a reference model:
    counts stay exact, non-negative, and conservation holds."""
    n_pages = 9
    a = PageAllocator(n_pages)
    model: dict[int, int] = {}          # page -> refcount (allocated only)
    held: list[int] = []                # multiset of references we hold
    rng = np.random.default_rng(0)
    for op, k in ops:
        if op == "alloc":
            got = a.alloc(k)
            if len(model) + k > n_pages - 1:
                assert got is None      # over-capacity: no partial grants
                continue
            assert got is not None and len(got) == k
            for pg in got:
                assert pg not in model  # never hand out a live page twice
                model[pg] = 1
                held.append(pg)
        elif op == "incref" and held:
            pg = held[int(rng.integers(len(held)))]
            a.incref([pg])
            model[pg] += 1
            held.append(pg)
        elif op == "free" and held:
            pg = held.pop(int(rng.integers(len(held))))
            a.free([pg])
            model[pg] -= 1
            if model[pg] == 0:
                del model[pg]
        # the invariants, after every single operation:
        for pg in range(1, n_pages):
            assert a.refcount(pg) == model.get(pg, 0)
            assert a.refcount(pg) >= 0
        assert a.n_free == (n_pages - 1) - len(model)


@given(st.integers(min_value=2, max_value=5))
def test_free_idempotent_under_sharing(extra_refs):
    """A shared page drops exactly one holder per free; once fully
    released, further frees are silent no-ops (never negative, never a
    duplicate free-list entry)."""
    a = PageAllocator(6)
    (pg,) = a.alloc(1)
    a.incref([pg] * (extra_refs - 1))
    for expect in range(extra_refs - 1, -1, -1):
        a.free([pg])
        assert a.refcount(pg) == expect
    assert a.n_free == 5
    for _ in range(3):
        a.free([pg])                    # already free: idempotent
        assert a.refcount(pg) == 0
        assert a.n_free == 5
        assert sorted(a._free) == [1, 2, 3, 4, 5]   # no duplicates


def test_incref_of_free_page_raises():
    a = PageAllocator(4)
    with pytest.raises(ValueError, match="incref"):
        a.incref([2])


# ------------------------------------------------------------- COW copy ---
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_cow_copy_preserves_other_holders_bitwise(seed):
    """The COW split: after copying a shared page to a fresh one and
    repointing ONE holder's table, the other holder's gathered dense
    view is bit-identical to before, and the mover's view is too (the
    copy is exact) — divergence only begins with the first post-split
    write."""
    rng = np.random.default_rng(seed)
    npr, P, bs, d = 2, 6, 4, 3
    pages = jnp.asarray(rng.standard_normal((npr, P, bs * d)), jnp.float32)
    bt_a = np.array([[1, 2]], np.int32)          # A shares page 2 with B
    bt_b = np.array([[3, 2]], np.int32)
    before_a = np.asarray(gather_pages(pages, 0, jnp.asarray(bt_a), (d,)))
    before_b = np.asarray(gather_pages(pages, 0, jnp.asarray(bt_b), (d,)))
    # split for B: copy page 2 -> fresh page 4, repoint B only
    src = jnp.asarray([2, TRASH_PAGE], jnp.int32)
    dst = jnp.asarray([4, TRASH_PAGE], jnp.int32)
    pages2 = copy_pages(pages, src, dst)
    bt_b2 = np.array([[3, 4]], np.int32)
    after_a = np.asarray(gather_pages(pages2, 0, jnp.asarray(bt_a), (d,)))
    after_b = np.asarray(gather_pages(pages2, 0, jnp.asarray(bt_b2), (d,)))
    assert (before_a == after_a).all()           # frozen original intact
    assert (before_b == after_b).all()           # the copy is exact
    # and a write into B's copy leaves A untouched
    pages3 = pages2.at[:, 4].set(0.0)
    assert (np.asarray(gather_pages(pages3, 0, jnp.asarray(bt_a), (d,)))
            == before_a).all()


# ----------------------------------------------------------- no aliasing --
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=1, max_value=24),
       st.integers(min_value=1, max_value=24))
def test_prefix_lookup_never_aliases_distinct_prefixes(seed, la, lb):
    """Register prompt A's blocks, look up random prompt B: every hit
    block's prefix must be byte-identical to A's — a differing token
    anywhere in the covered prefix kills the hit for that block and all
    later blocks whose keys embed it."""
    bs = 4
    rng = np.random.default_rng(seed)
    a_tok = rng.integers(0, 4, (la,)).astype(np.int32)   # tiny vocab:
    b_tok = rng.integers(0, 4, (lb,)).astype(np.int32)   # collisions likely
    alloc = PageAllocator(32)
    cache = PrefixCache(alloc, bs)
    n_blocks_a = -(-la // bs)
    pages_a = alloc.alloc(n_blocks_a)
    cache.insert(a_tok, pages_a)
    shared, n_cached = cache.lookup(b_tok)
    for b, pg in enumerate(shared):
        if pg is None:
            continue
        end = min((b + 1) * bs, lb)
        assert end <= la
        assert (b_tok[:end] == a_tok[:end]).all(), (a_tok, b_tok, b)
        assert pg == pages_a[b]
    # and the cached-token count is consistent with the hits
    assert n_cached == sum(
        min((b + 1) * bs, lb) - b * bs
        for b, pg in enumerate(shared) if pg is not None)


# ------------------------------------------------------------ liveness ----
def test_stale_entries_self_heal_and_resubmit_misses():
    """Regression (staleness under eviction): lookup results must be
    backed by live, refcounted pages.  Adversarial trace — register a
    prompt, yank the index's own references out from under it (the
    over-free bug class the scheduler's ``_release`` discipline now
    prevents at the source), then resubmit: the recycled pages must
    never be served as cached KV; the dead entries self-heal instead."""
    bs = 4
    alloc = PageAllocator(8)
    cache = PrefixCache(alloc, bs)
    tok = np.arange(1, 9, dtype=np.int32)        # exactly 2 full blocks
    pages = alloc.alloc(2)
    cache.insert(tok, pages)                     # index: one ref per block
    alloc.free(pages)                            # the producer departs
    assert [alloc.refcount(p) for p in pages] == [1, 1]
    live, n = cache.lookup(tok)
    assert live == pages and n == 8              # healthy: still served
    alloc.free(pages)                            # adversarial over-free
    assert alloc.n_free == 7                     # pages back in the pool
    gen = cache.generation
    assert cache.peek_cached_tokens(tok) == 0    # probe sees them dead...
    assert cache.generation == gen               # ...without mutating
    shared, n = cache.lookup(tok)
    assert shared == [None, None] and n == 0     # stale: dropped, not served
    assert cache.stale_drops == 2
    assert cache.generation > gen                # peek memos invalidated
    # resubmission re-registers cleanly: served again, live refs
    # (producer's + the index's)
    cache.insert(tok, alloc.alloc(2))
    shared, n = cache.lookup(tok)
    assert n == 8 and all(alloc.refcount(p) == 2 for p in shared)


# ---------------------------------------------- scheduler-level sharing ---
@given(st.lists(st.integers(1, 14), min_size=2, max_size=5),
       st.sampled_from([None, 5, 8]),
       st.booleans())
def test_refcounts_never_negative_under_evict_cow_preempt(lens, window,
                                                          same_prefix):
    """Interleaved window evictions, COW splits and LIFO preemptions (a
    pool of 5 pages for up to 5 rows forces all three) against the
    allocator invariants: no refcount ever dips negative, every page a
    running row's block table points at stays live, and free pages +
    referenced pages partition the pool after every scheduler call.
    ``window=None`` runs the prefix-sharing/COW side; a set window runs
    the eviction side (where registration is disabled by design)."""
    from repro.serve.kv_cache import PagedCacheConfig
    from repro.serve.scheduler import Request, Scheduler

    bs, max_blocks = 4, 4
    pcfg = PagedCacheConfig(page_size=bs, n_pages=1 + max_blocks + 1,
                            max_seqs=2, max_blocks=max_blocks,
                            resident_blocks=None if window is None else 3)
    sched = Scheduler(pcfg, prefix_cache=window is None, chunked=True,
                      token_budget=6, chunk_size=bs, prefill_reserve=3,
                      window_tokens=window)
    rng = np.random.default_rng(11)
    base = rng.integers(1, 9, (14,)).astype(np.int32)
    for i, L in enumerate(lens):
        tok = (base[:L].copy() if same_prefix
               else rng.integers(1, 99, (L,)).astype(np.int32))
        sched.submit(Request(rid=i, tokens=tok, max_new=2))

    def check():
        for s in sched.running.values():
            for pg in s.pages:
                if pg != TRASH_PAGE:
                    assert sched.alloc.refcount(pg) >= 1, pg
        n_ref = 0
        for pg in range(1, pcfg.n_pages):
            rc = sched.alloc.refcount(pg)
            assert rc >= 0, pg
            n_ref += rc > 0
        assert sched.alloc.n_free + n_ref == pcfg.n_pages - 1

    steps = 0
    while sched.has_work:
        steps += 1
        assert steps <= 400, "scheduler loop did not terminate"
        sched.schedule()
        check()
        for s in sched.plan_mixed(1):
            seq = s.seq
            if s.kind == "chunk":
                sched.register_chunks(seq)
                if s.last:
                    seq.emitted = [1]
                    seq.last_token = 1
            else:
                seq.emitted.append(1)
                seq.length += 1
        for seq in list(sched.running.values()):
            if seq.emitted and len(seq.emitted) >= seq.req.max_new:
                sched.complete(seq)
                check()
    check()


@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_prefix_partial_tail_requires_exact_whole_prompt(seed):
    """The partial-tail entry hits only on an exact whole-prompt match:
    a prompt that extends or truncates the registered one differently
    must miss the tail (full-block hits are still allowed)."""
    bs = 4
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 6, (10,)).astype(np.int32)    # 2 full + tail(2)
    alloc = PageAllocator(16)
    cache = PrefixCache(alloc, bs)
    pages = alloc.alloc(3)
    cache.insert(base, pages)
    # same prompt: full hit incl. partial tail
    shared, n = cache.lookup(base)
    assert shared == pages and n == 10
    # one token longer: tail key differs -> tail misses
    longer = np.concatenate([base, [1]]).astype(np.int32)
    shared, n = cache.lookup(longer)
    assert shared[:2] == pages[:2] and shared[2] is None and n == 8
    # divergent last token: tail misses
    div = base.copy()
    div[-1] = (div[-1] + 1) % 6
    shared, n = cache.lookup(div)
    assert shared[2] is None and n == 8
