"""Paged-KV runtime primitives: slot math, pooled write/gather, hypothesis
property tests of the paging invariants."""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collect-and-skip fallback (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.core.paged.kv_cache import (
    gather_pages, physical_slots, write_pages,
)


def test_physical_slots_basic():
    pt = jnp.asarray([[3, 1, 2], [5, 4, 0]], jnp.int32)
    pos = jnp.asarray([[0, 16, 33], [5, -1, 0]], jnp.int32)
    valid = jnp.asarray([[True, True, True], [True, False, True]])
    slots = physical_slots(pt, pos, valid, page_size=16, pages_per_pool=8)
    # seq0: pos0 -> page3 slot0=48; pos16 -> page1*16=16; pos33 -> page2*16+1
    np.testing.assert_array_equal(
        np.asarray(slots), [[48, 16, 33], [85, 128, 80]]
    )  # invalid -> 8*16 = 128 (trash)


def test_write_then_gather_roundtrip_multi_pool():
    rng = np.random.default_rng(0)
    layers, hkv, pools, p, ps, d = 3, 2, 2, 5, 4, 8
    s, t = 4, 6  # 2 seqs per pool
    pages = jnp.zeros((layers, hkv, pools, p, ps, d), jnp.float32)
    pt = jnp.asarray([[1, 2], [3, 4], [2, 1], [4, 3]], jnp.int32)
    new = jnp.asarray(rng.standard_normal((s, t, hkv, d)), jnp.float32)
    pos = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None], (s, 1))
    valid = jnp.asarray([[True] * 6, [True] * 3 + [False] * 3,
                         [True] * 6, [False] * 6])
    slots = physical_slots(pt, pos, valid, ps, p)
    out = write_pages(pages, 1, new, slots)
    np.testing.assert_array_equal(np.asarray(out)[[0, 2]], 0.0)
    dense = gather_pages(out, 1, pt)  # [S, Np*ps, Hkv, D]
    for si in range(s):
        for ti in range(t):
            got = np.asarray(dense[si, ti])
            want = np.asarray(new[si, ti]) if bool(valid[si, ti]) \
                else np.zeros((hkv, d))
            np.testing.assert_allclose(got, want)


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_paging_invariant_permutation(data):
    """Property: any permutation of physical pages (with the table updated
    to match) yields identical gathered KV — the indirection is exact."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**30)))
    hkv, ps, d = 2, 4, 4
    np_ = data.draw(st.integers(1, 4))
    s = data.draw(st.integers(1, 3))
    p = s * np_ + 1
    kv = jnp.asarray(rng.standard_normal((1, hkv, 1, p, ps, d)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(p - 1)[: s * np_].reshape(s, np_) + 1, jnp.int32)
    base = np.asarray(gather_pages(kv, 0, pt))

    perm = rng.permutation(p - 1) + 1  # permute non-null pages
    inv = np.zeros(p, np.int64)
    inv[perm] = np.arange(1, p)
    kv2 = jnp.asarray(np.asarray(kv)[:, :, :, np.concatenate([[0], perm])])
    pt2 = jnp.asarray(inv[np.asarray(pt)], jnp.int32)
    np.testing.assert_allclose(np.asarray(gather_pages(kv2, 0, pt2)), base)


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_writes_never_leak_across_sequences(data):
    """Property: writing seq A's tokens never changes what seq B reads."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**30)))
    hkv, ps, d, np_ = 1, 4, 4, 3
    s, p = 3, 10
    kv = jnp.asarray(rng.standard_normal((1, hkv, 1, p, ps, d)), jnp.float32)
    pt = jnp.asarray(
        rng.permutation(p - 1)[: s * np_].reshape(s, np_) + 1, jnp.int32)
    before = np.asarray(gather_pages(kv, 0, pt))
    writer = data.draw(st.integers(0, s - 1))
    t = data.draw(st.integers(1, np_ * ps))
    new = jnp.asarray(rng.standard_normal((s, t, hkv, d)), jnp.float32)
    pos = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None], (s, 1))
    valid = jnp.zeros((s, t), bool).at[writer].set(True)
    slots = physical_slots(pt, pos, valid, ps, p)
    after = np.asarray(gather_pages(write_pages(kv, 0, new, slots), 0, pt))
    for si in range(s):
        if si == writer:
            np.testing.assert_allclose(after[si, :t], np.asarray(new[si]))
            np.testing.assert_allclose(after[si, t:], before[si, t:])
        else:
            np.testing.assert_allclose(after[si], before[si])
