"""Paged KV cache: allocator, gather/scatter, bit-for-bit vs dense."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.models.attention import decode_attention
from repro.serve.kv_cache import (
    TRASH_PAGE,
    PageAllocator,
    PagedCacheConfig,
    flat_pages,
    gather_pages,
    page_view,
    write_prompt_pages,
    write_token,
)


def test_allocator_alloc_free_utilization():
    a = PageAllocator(9)            # 8 usable, page 0 reserved
    assert a.n_free == 8 and a.utilization == 0.0
    got = a.alloc(3)
    assert len(got) == 3 and TRASH_PAGE not in got
    assert a.utilization == pytest.approx(3 / 8)
    assert a.alloc(6) is None       # not enough: no partial allocation
    assert a.n_free == 5
    a.free(got)
    assert a.n_free == 8
    with pytest.raises(ValueError):
        a.free([TRASH_PAGE])        # trash page is never allocatable
    with pytest.raises(ValueError):
        a.free([a.n_pages])         # out of range is a real bug: raises


def test_allocator_free_is_idempotent():
    """Preempt-then-complete may release the same pages twice in one
    engine step; the free list must not grow duplicates (a duplicate
    would hand one physical page to two sequences)."""
    a = PageAllocator(9)
    got = a.alloc(3)
    a.free(got)
    a.free(got)                     # second release: silent no-op
    assert a.n_free == 8
    assert sorted(a._free) == list(range(1, 9))   # no duplicates
    # a page re-allocated after the double release is handed out once
    again = a.alloc(8)
    assert sorted(again) == list(range(1, 9))
    assert a.alloc(1) is None


def test_paged_config_validates():
    with pytest.raises(ValueError):
        PagedCacheConfig(page_size=4, n_pages=3, max_seqs=1, max_blocks=4)


def _paged_from_dense(dense, bs, rng):
    """Scatter a dense [R, S, ...] cache into a shuffled one-layer pool
    of flat pages [1, P, bs*F]."""
    R, S = dense.shape[:2]
    nb = S // bs
    perm = rng.permutation(np.arange(1, 1 + R * nb))
    bt = perm.reshape(R, nb).astype(np.int32)
    pages = np.zeros((1 + R * nb, bs) + dense.shape[2:], dense.dtype)
    for r in range(R):
        for b in range(nb):
            pages[bt[r, b]] = dense[r, b * bs:(b + 1) * bs]
    pool = flat_pages(pages, dense.shape[2:])[None]
    return jnp.asarray(pool), jnp.asarray(bt)


def test_gather_pages_equals_dense_bitwise():
    rng = np.random.default_rng(0)
    dense = rng.standard_normal((3, 32, 2, 4)).astype(np.float32)
    pages, bt = _paged_from_dense(dense, bs=8, rng=rng)
    out = np.asarray(gather_pages(pages, 0, bt, (2, 4)))
    assert out.shape == dense.shape
    assert (out == dense).all()     # bit-for-bit


def test_paged_attention_read_equals_dense_bitwise():
    """The acceptance gate: block-table gather feeding decode attention
    produces bit-identical output to the dense-cache read."""
    rng = np.random.default_rng(1)
    R, S, Hk, D, G = 4, 64, 2, 16, 3
    dense_k = rng.standard_normal((R, S, Hk, D)).astype(np.float32)
    dense_v = rng.standard_normal((R, S, Hk, D)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((R, 1, Hk * G, D)), jnp.float32)
    lengths = jnp.asarray([7, 33, 60, 1], jnp.int32)
    kp, bt = _paged_from_dense(dense_k, bs=16, rng=rng)
    # v pages must share k's block table: scatter v along the same mapping
    btn = np.asarray(bt)
    vpages = np.zeros((1 + R * (S // 16), 16, Hk, D), np.float32)
    for r in range(R):
        for b in range(S // 16):
            vpages[btn[r, b]] = dense_v[r, b * 16:(b + 1) * 16]
    vp = jnp.asarray(flat_pages(vpages, (Hk, D))[None])
    out_d, lse_d = decode_attention(q, jnp.asarray(dense_k),
                                    jnp.asarray(dense_v), lengths)
    out_p, lse_p = decode_attention(q, gather_pages(kp, 0, bt, (Hk, D)),
                                    gather_pages(vp, 0, bt, (Hk, D)),
                                    lengths)
    assert (np.asarray(out_d) == np.asarray(out_p)).all()
    assert (np.asarray(lse_d) == np.asarray(lse_p)).all()


def test_write_token_lands_at_length():
    rng = np.random.default_rng(2)
    R, nb, bs, layers = 3, 2, 4, 3
    pages = jnp.zeros((layers, 1 + R * nb, bs * 2), jnp.float32)
    bt = jnp.asarray(np.arange(1, 1 + R * nb).reshape(R, nb), jnp.int32)
    lengths = jnp.asarray([0, 3, 5], jnp.int32)     # row 2 in block 1
    vals = jnp.asarray(rng.standard_normal((R, 2)), jnp.float32)
    pages = write_token(pages, 1, bt, lengths, vals)
    dense = np.asarray(gather_pages(pages, 1, bt, (2,)))  # [R, nb*bs, 2]
    for r, t in enumerate([0, 3, 5]):
        assert (dense[r, t] == np.asarray(vals)[r]).all()
        mask = np.ones(nb * bs, bool)
        mask[t] = False
        assert (dense[r, mask] == 0).all()          # nothing else touched
    # the other layers of the stacked pool are not touched either
    assert (np.asarray(pages)[[0, 2]] == 0).all()


def test_write_prompt_pages_blits_and_trash_pads():
    rng = np.random.default_rng(3)
    npr, P, bs, d = 2, 6, 4, 3
    pages = jnp.zeros((npr, P, bs * d), jnp.float32)
    planes = jnp.asarray(rng.standard_normal((npr, 1, 8, d)), jnp.float32)
    block_row = jnp.asarray([2, 5], jnp.int32)
    pages = write_prompt_pages(pages, block_row, planes)
    got = np.asarray(page_view(pages, (d,)))
    want = np.asarray(planes).reshape(npr, 2, bs, d)
    assert (got[:, 2] == want[:, 0]).all()
    assert (got[:, 5] == want[:, 1]).all()
    assert (got[:, 1] == 0).all() and (got[:, 3] == 0).all()
    # unused logical blocks redirect to trash: in-bounds, harmless
    trash_row = jnp.asarray([1, TRASH_PAGE], jnp.int32)
    pages2 = write_prompt_pages(pages, trash_row, planes)
    assert (np.asarray(page_view(pages2, (d,)))[:, 1] == want[:, 0]).all()
