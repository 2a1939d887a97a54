"""Paged-attention kernels vs the pure-jnp oracle: shape/dtype sweeps,
ragged contexts, GQA ratios, non-power-of-two pages, static-grid masking,
and the in-place pool write.  Every kernel reads the whole stacked pool
[L, Hkv, P, ps, 2D] at a layer index; the cases fill every layer with
different values, so reading the wrong layer fails."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # kernel sweep: excluded from -m \"not slow\"
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collect-and-skip fallback (requirements-dev.txt)
    from _hypothesis_fallback import given, settings, st

from repro.kernels.paged_attention import ops, ref


LAYERS = 2  # layer indices 0 and L - 1 are both read


def make_pool(rng, hkv, p, ps, d, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal((LAYERS, hkv, p, ps, 2 * d)),
                       dtype)


def make_case(rng, s, hq, hkv, d, ps, np_, ctx, dtype=jnp.float32):
    p = s * np_ + 1
    q = jnp.asarray(rng.standard_normal((s, hq, d)), dtype)
    kv = make_pool(rng, hkv, p, ps, d, dtype)
    pt = jnp.asarray(
        rng.permutation(p - 1)[: s * np_].reshape(s, np_) + 1, jnp.int32
    )
    ctx = jnp.asarray(ctx, jnp.int32)
    return q, kv, pt, ctx


DECODE_CASES = [
    # (S, Hq, Hkv, D, page_size, pages_per_seq, ctx_lens, dtype, tol)
    (4, 8, 2, 128, 16, 6, [37, 1, 0, 96], jnp.float32, 2e-5),
    (2, 4, 4, 64, 16, 4, [64, 13], jnp.float32, 2e-5),  # MHA, padded head_dim
    (3, 16, 1, 128, 32, 4, [128, 5, 77], jnp.float32, 2e-5),  # MQA
    (2, 9, 3, 64, 8, 8, [55, 64], jnp.float32, 2e-5),  # smollm ratios
    (2, 8, 2, 128, 24, 4, [96, 17], jnp.float32, 2e-5),  # non-pow2 page (C4)
    (4, 8, 2, 128, 16, 6, [37, 1, 0, 96], jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("variant", ["baseline", "gqa", "segmented"])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_matches_oracle(variant, case, layer):
    s, hq, hkv, d, ps, np_, ctx, dtype, tol = case
    rng = np.random.default_rng(hash((variant, s, hq, d)) % 2**31)
    q, kv, pt, ctxa = make_case(rng, s, hq, hkv, d, ps, np_, ctx, dtype)
    expected = ref.paged_attention_decode_ref(q, kv, layer, pt, ctxa)
    got = ops.paged_attention_decode(q, kv, layer, pt, ctxa,
                                     variant=variant)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expected, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("tile", [8, 16])
@pytest.mark.parametrize("nseg", [1, 2, 8, 64])
def test_decode_adjustable_tiles_and_segments(tile, nseg):
    """C4: tile decoupled from page size; C3: any segment count."""
    rng = np.random.default_rng(7)
    s, hq, hkv, d, ps, np_ = 3, 8, 2, 128, 16, 8
    q, kv, pt, ctx = make_case(rng, s, hq, hkv, d, ps, np_, [128, 3, 51])
    expected = ref.paged_attention_decode_ref(q, kv, 1, pt, ctx)
    got = ops.paged_attention_decode(
        q, kv, 1, pt, ctx, variant="segmented", tile=tile, num_segments=nseg
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=2e-5, rtol=2e-5
    )


def test_decode_static_grid_dead_seqs_zero():
    """C5: padded (dead) sequences must produce exact zeros."""
    rng = np.random.default_rng(8)
    q, kv, pt, ctx = make_case(rng, 4, 8, 2, 128, 16, 4, [10, 0, 0, 7])
    for variant in ("baseline", "gqa", "segmented"):
        got = np.asarray(
            ops.paged_attention_decode(q, kv, 0, pt, ctx, variant=variant)
        )
        assert (got[1] == 0).all() and (got[2] == 0).all(), variant
        assert np.isfinite(got).all(), variant


@settings(max_examples=20, deadline=None)
@given(
    s=st.integers(1, 5),
    hkv=st.sampled_from([1, 2, 3]),
    group=st.sampled_from([1, 2, 4]),
    np_=st.integers(1, 5),
    data=st.data(),
)
def test_decode_property_random_ragged(s, hkv, group, np_, data):
    """Property: for random ragged context lengths the kernel equals the
    dense-gather oracle (paged gather == dense attention)."""
    ps, d = 16, 64
    ctx = data.draw(
        st.lists(st.integers(0, np_ * ps), min_size=s, max_size=s)
    )
    rng = np.random.default_rng(data.draw(st.integers(0, 2**30)))
    layer = data.draw(st.integers(0, LAYERS - 1))
    q, kv, pt, ctxa = make_case(rng, s, hkv * group, hkv, d, ps, np_, ctx)
    expected = ref.paged_attention_decode_ref(q, kv, layer, pt, ctxa)
    got = ops.paged_attention_decode(q, kv, layer, pt, ctxa, variant="gqa")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=3e-5, rtol=3e-5
    )


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def make_prefill_case(rng, qlens, ctx_prior, hq, hkv, d, ps, np_, t_pad,
                      dtype=jnp.float32):
    s = len(qlens)
    p = s * np_ + 1
    qlens = jnp.asarray(qlens, jnp.int32)
    ctx = jnp.asarray(ctx_prior, jnp.int32) + qlens
    qsl = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(qlens)])
    q = jnp.asarray(rng.standard_normal((t_pad, hq, d)), dtype)
    kv = make_pool(rng, hkv, p, ps, d, dtype)
    pt = jnp.asarray(
        rng.permutation(p - 1)[: s * np_].reshape(s, np_) + 1, jnp.int32
    )
    return q, kv, pt, ctx, qsl, qlens


PREFILL_CASES = [
    # (qlens, ctx_prior, Hq, Hkv, D, ps, Np, T_pad, block_q)
    ([17, 0, 33], [23, 0, 0], 4, 2, 128, 16, 8, 64, 8),
    ([32], [0], 8, 2, 64, 16, 4, 32, 16),  # pure prefill
    ([5, 9, 2], [11, 0, 3], 4, 4, 128, 8, 8, 32, 4),  # MHA chunked
    ([16, 16], [16, 48], 16, 1, 128, 32, 4, 32, 16),  # MQA chunked
    ([31], [0], 9, 3, 64, 24, 4, 32, 8),  # non-pow2 page
]


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("case", PREFILL_CASES)
def test_prefill_matches_oracle(case, layer):
    qlens, ctxp, hq, hkv, d, ps, np_, t_pad, bq = case
    rng = np.random.default_rng(hash(tuple(qlens)) % 2**31)
    q, kv, pt, ctx, qsl, ql = make_prefill_case(
        rng, qlens, ctxp, hq, hkv, d, ps, np_, t_pad
    )
    expected = ref.paged_attention_prefill_ref(q, kv, layer, pt, ctx, qsl,
                                               ql)
    got = ops.paged_attention_prefill(
        q, kv, layer, pt, ctx, qsl, ql, block_q=bq
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), atol=3e-5, rtol=3e-5
    )


def test_prefill_dead_rows_zero():
    rng = np.random.default_rng(9)
    q, kv, pt, ctx, qsl, ql = make_prefill_case(
        rng, [10, 5], [0, 0], 4, 2, 64, 16, 4, 32, jnp.float32
    )
    got = np.asarray(
        ops.paged_attention_prefill(q, kv, 1, pt, ctx, qsl, ql, block_q=8)
    )
    assert (got[15:] == 0).all()
    assert np.isfinite(got).all()


def test_qblock_metadata_binary_search():
    """§6.1: cumulative Q-block tensor + binary search recovers the seq."""
    qsl = jnp.asarray([0, 17, 17, 50], jnp.int32)
    ql = jnp.asarray([17, 0, 33], jnp.int32)
    ctx = jnp.asarray([20, 0, 33], jnp.int32)
    qb_seq, qb_pos0, qb_row0, qb_rows = ops.build_qblock_metadata(
        qsl, ql, ctx, block_q=8, num_q_blocks=10
    )
    qb_seq = np.asarray(qb_seq)
    # seq0: ceil(17/8)=3 blocks; seq1: 0; seq2: ceil(33/8)=5 blocks
    assert list(qb_seq[:8]) == [0, 0, 0, 2, 2, 2, 2, 2]
    assert list(qb_seq[8:]) == [-1, -1]
    assert list(np.asarray(qb_rows)[:8]) == [8, 8, 1, 8, 8, 8, 8, 1]
    # first token of seq0 is at absolute position ctx-qlen = 3
    assert np.asarray(qb_pos0)[0] == 3
    assert np.asarray(qb_row0)[3] == 17  # seq2 rows start at qsl[2]=17


def test_segment_merge_associativity():
    """Property: merging per-segment partials == full softmax (paper §4.5)."""
    rng = np.random.default_rng(10)
    g, d, l, nseg = 4, 32, 64, 4
    s = jnp.asarray(rng.standard_normal((g, l)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((l, d)), jnp.float32)
    full = jax.nn.softmax(s, axis=-1) @ v
    seg = s.reshape(g, nseg, l // nseg)
    m_seg = jnp.max(seg, axis=-1)  # [g, nseg]
    p = jnp.exp(seg - m_seg[..., None])
    l_seg = jnp.sum(p, axis=-1)
    o_seg = jnp.einsum("gnk,nkd->ngd", p, v.reshape(nseg, l // nseg, d))
    merged = ref.merge_segments_ref(
        o_seg[None], m_seg.T[None], l_seg.T[None]
    )[0]
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(full), atol=1e-5, rtol=1e-5
    )


# ---------------------------------------------------------------------------
# in-place pool write
# ---------------------------------------------------------------------------


def _write_case(ps, p):
    """Slots as a packed step lays them out: two decode rows, a chunk
    that starts mid-block and crosses a page, padding (trash slots)
    between runs, and a chunk that fills one block exactly.  Pages 1-2
    stand for pages a prefix cache shares: no slot points into them."""
    trash = p * ps
    runs = [
        [5 * ps + 3],  # decode row
        [9 * ps + ps - 1],  # decode row at a page's last slot
        list(range(6 * ps + 5, 6 * ps + ps)) + list(range(7 * ps,
                                                          7 * ps + 9)),
        [trash] * 3,  # padded rows
        list(range(8 * ps, 8 * ps + 16)),
    ]
    return np.asarray([x for r in runs for x in r], np.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("layer", [0, LAYERS - 1])
def test_write_rows_matches_oracle(layer, d, dtype):
    """`write_rows` (Pallas, in place) == the `.at[].set` oracle: rows at
    their slots, trash slots dropped, every other row untouched (shared
    prefix pages, other layers)."""
    rng = np.random.default_rng(11)
    hkv, ps, p = 2, 32, 12
    pool = make_pool(rng, hkv, p, ps, d, dtype)
    slots = _write_case(ps, p)
    rows = jnp.asarray(rng.standard_normal((len(slots), hkv, 2 * d)), dtype)
    want = ref.write_rows_ref(pool, layer, rows, jnp.asarray(slots))
    plan = ops.write_plan(jnp.asarray(slots), ps, p)
    assert int(plan["num_blocks"]) == 6
    got = ops.write_rows(pool, layer, rows, plan)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    before, after = np.asarray(pool), np.asarray(got)
    np.testing.assert_array_equal(after[1 - layer], before[1 - layer])
    np.testing.assert_array_equal(after[:, :, 1:3], before[:, :, 1:3])
