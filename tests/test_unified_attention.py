"""Unified token-packed attention step: one launch for decode + fresh +
resumed prefill.

Packing is an EXECUTION-LAYOUT change — its only acceptable observable
effect is which executables are compiled and how many token rows they
launch, never WHAT is computed.  The engine suite here is differential:
the same request set runs through a packed (default) and a padded
(`packed_attention=False`) engine and outputs must match token for token
— across chunked prefill, prefix-cache hits, mixed decode+prefill steps,
and both backends — while the harness checks budget and allocator
page-conservation invariants on every step.  Op-level tests pin the
kernel contract: the unified launch is bit-identical to the separate
decode/prefill launches it replaces, and the xla ragged reference matches
the pallas Q-Block kernel on the same packed metadata.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import serving_harness as H
from repro.core.attention import backend as attn_backend
from repro.core.attention import heuristics
from repro.core.paged import kv_cache as KV
from repro.kernels.paged_attention import ops, ref

BUDGET = 16


@pytest.fixture(scope="module")
def smollm():
    return H.build_cfg_params()


# ---------------------------------------------------------------------------
# op level: the packed launch vs the launches it replaces
# ---------------------------------------------------------------------------


def make_packed_case(rng, dec_ctx, qlens_pref, ctx_prior, *, hq=4, hkv=2,
                     d=64, ps=16, np_=4):
    """A token-packed batch: decode rows first (one per slot, q == 1,
    dead slots ctx == 0), then ragged chunks (fresh and resumed)."""
    nd = len(dec_ctx)
    s = nd + len(qlens_pref)
    t = nd + sum(qlens_pref)
    p = s * np_ + 1
    qlens = np.array([1] * nd + list(qlens_pref), np.int32)
    ctx = np.array(list(dec_ctx)
                   + [c + q for c, q in zip(ctx_prior, qlens_pref)],
                   np.int32)
    qsl = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((t, hq, d)), jnp.float32)
    # the stacked pool of two layers; the op tests read layer 1
    kv = jnp.asarray(rng.standard_normal((2, hkv, p, ps, 2 * d)),
                     jnp.float32)
    pt = jnp.asarray(
        rng.permutation(p - 1)[: s * np_].reshape(s, np_) + 1, jnp.int32)
    return (q, kv, pt, jnp.asarray(ctx), jnp.asarray(qsl),
            jnp.asarray(qlens), nd)


def test_unified_op_bit_identical_to_separate_launches():
    """The q == 1 rows run the decode kernel, the chunks the Q-Block
    kernel — the packed launch must reproduce the separate launches it
    replaces BIT-identically (same kernels, same inputs)."""
    rng = np.random.default_rng(0)
    q, kv, pt, ctx, qsl, ql, nd = make_packed_case(
        rng, dec_ctx=[37, 0, 52], qlens_pref=[9, 17], ctx_prior=[0, 23])
    uni = ops.paged_attention_unified(
        q, kv, 1, pt, ctx, qsl, ql, num_decode_seqs=nd, block_q=8)
    dec = ops.paged_attention_decode(
        q[:nd], kv, 1, pt[:nd], ctx[:nd], variant="gqa")
    pre = ops.paged_attention_prefill(
        q[nd:], kv, 1, pt[nd:], ctx[nd:], qsl[nd:] - nd, ql[nd:],
        block_q=8)
    np.testing.assert_array_equal(np.asarray(uni[:nd]), np.asarray(dec))
    np.testing.assert_array_equal(np.asarray(uni[nd:]), np.asarray(pre))


def test_unified_op_matches_ragged_oracle():
    """Against the pure-jnp ragged oracle, which treats a decode row as a
    1-token segment — the generalization the unified layout leans on."""
    rng = np.random.default_rng(1)
    q, kv, pt, ctx, qsl, ql, nd = make_packed_case(
        rng, dec_ctx=[21, 64, 0, 5], qlens_pref=[13, 32, 1],
        ctx_prior=[0, 16, 30])
    expected = ref.paged_attention_prefill_ref(q, kv, 1, pt, ctx, qsl, ql)
    for variant in ("gqa", "segmented"):
        got = ops.paged_attention_unified(
            q, kv, 1, pt, ctx, qsl, ql, num_decode_seqs=nd,
            variant=variant, block_q=8)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), atol=3e-5, rtol=3e-5)


def test_ragged_xla_backend_matches_pallas():
    """The satellite fix: `backend='xla'` must run a REAL xla ragged
    reference (it used to silently run the pallas path), and both
    backends must agree on the same packed metadata — including q == 1
    rows, which only the unified entry routes to the decode kernel."""
    rng = np.random.default_rng(2)
    q, kv, pt, ctx, qsl, ql, nd = make_packed_case(
        rng, dec_ctx=[18, 0], qlens_pref=[7, 24], ctx_prior=[9, 0])
    kv6 = kv[:, :, None]  # add the (single) pool axis
    out_xla = attn_backend.prefill_attention_ragged(
        "xla", q, kv6, 1, pt, ctx, qsl, ql)
    out_pal = attn_backend.prefill_attention_ragged(
        "pallas", q, kv6, 1, pt, ctx, qsl, ql,
        kernel_cfg=heuristics.KernelConfig("gqa", block_q=8))
    np.testing.assert_allclose(
        np.asarray(out_xla), np.asarray(out_pal), atol=3e-5, rtol=3e-5)
    # unified entry point: same agreement with the decode split active
    uni_xla = attn_backend.unified_attention(
        "xla", q, kv6, 1, pt, ctx, qsl, ql, num_decode_seqs=nd)
    uni_pal = attn_backend.unified_attention(
        "pallas", q, kv6, 1, pt, ctx, qsl, ql, num_decode_seqs=nd,
        kernel_cfg=heuristics.KernelConfig("gqa", block_q=8))
    np.testing.assert_allclose(
        np.asarray(uni_xla), np.asarray(uni_pal), atol=3e-5, rtol=3e-5)


def test_ragged_multi_pool_is_a_hard_error():
    rng = np.random.default_rng(3)
    q, kv, pt, ctx, qsl, ql, nd = make_packed_case(
        rng, dec_ctx=[8], qlens_pref=[4], ctx_prior=[0])
    two_pools = jnp.stack([kv, kv], axis=2)
    for backend in ("xla", "pallas"):
        with pytest.raises(KV.ShardingError, match="num_pools=2"):
            attn_backend.prefill_attention_ragged(
                backend, q, two_pools, 0, pt, ctx, qsl, ql)


# ---------------------------------------------------------------------------
# engine level: packed == padded, token for token
# ---------------------------------------------------------------------------


def _pair(cfg, params, prompts, *, max_new_tokens=6, **kw):
    """(padded, packed) runs of the same request set."""
    runs = []
    for packed in (False, True):
        eng = H.build_engine(cfg, params, packed_attention=packed, **kw)
        runs.append(H.run_requests(eng, [list(p) for p in prompts],
                                   max_new_tokens=max_new_tokens))
    return runs


def test_packed_equivalence_mixed_decode_fresh(smollm):
    """Plain engine: a fresh prefill lands while earlier requests decode
    (staggered finish lengths force the overlap) — packed steps mix
    q == 1 rows with chunks and match the padded engine and the dense
    ground truth."""
    from repro.serving.request import make_requests
    cfg, params = smollm
    rng = np.random.default_rng(0)
    prompts = H.make_prompts(cfg, rng, (17, 5, 33, 9, 21))

    def run(packed):
        eng = H.build_engine(cfg, params, packed_attention=packed)
        reqs = make_requests([list(p) for p in prompts])
        for i, r in enumerate(reqs):
            r.max_new_tokens = 3 + 2 * i  # staggered finishes
        for r in reqs:
            eng.add_request(r)
        stats = []
        while eng.sched.has_work and len(stats) < 200:
            st = eng.step()
            stats.append(st)
            H.assert_step_invariants(eng, st)
        return eng, reqs, stats

    (_, reqs_pad, _), (eng, reqs_pack, stats) = run(False), run(True)
    for i, (ra, rb) in enumerate(zip(reqs_pad, reqs_pack)):
        assert ra.output == rb.output, f"request {i} diverged"
    assert any(s["decode"] > 0 and s["prefill"] > 0 for s in stats), \
        "no step mixed the phases"
    assert reqs_pack[0].output == H.greedy_reference(
        cfg, params, prompts[0], 3)


def test_packed_equivalence_chunked(smollm):
    """Chunked prefill: every resumed chunk rides the same unified launch
    as the decodes it shares the step with."""
    cfg, params = smollm
    rng = np.random.default_rng(1)
    prompts = H.make_prompts(cfg, rng, (3 * BUDGET + 12, 9, 2 * BUDGET + 5))
    padded, packed = _pair(cfg, params, prompts,
                           enable_chunked_prefill=True,
                           max_prefill_tokens=BUDGET)
    H.assert_same_outputs(padded, packed, label_a="padded",
                          label_b="packed")
    assert packed.total("partial_prefills") >= 3


def test_packed_equivalence_prefix_cache(smollm):
    """Prefix-cache hits resume mid-prompt inside the packed stream; hit
    accounting is identical to the padded engine."""
    cfg, params = smollm
    rng = np.random.default_rng(2)
    prompts = H.shared_prefix_prompts(cfg, rng, 48, (7, 12, 9, 5))
    padded, packed = _pair(cfg, params, prompts, max_seqs=2,
                           enable_prefix_caching=True)
    H.assert_same_outputs(padded, packed, label_a="padded",
                          label_b="packed")
    assert packed.engine.cached_prefill_tokens \
        == padded.engine.cached_prefill_tokens > 0
    assert packed.engine.prefilled_tokens == padded.engine.prefilled_tokens


def test_packed_equivalence_chunked_cached_preempting(smollm):
    """The full stack at once: chunked + cached + a starved pool forcing
    preempt-resume — packed == padded through donation and re-admission."""
    cfg, params = smollm
    rng = np.random.default_rng(3)
    prompts = H.make_prompts(cfg, rng, (3 * BUDGET + 10, 3 * BUDGET + 2))
    padded, packed = _pair(cfg, params, prompts, max_seqs=2, num_pages=8,
                           max_model_len=128, max_new_tokens=8,
                           enable_chunked_prefill=True,
                           enable_prefix_caching=True,
                           max_prefill_tokens=BUDGET)
    H.assert_same_outputs(padded, packed, label_a="padded",
                          label_b="packed")
    assert packed.total("preempted") > 0, "pool never starved"


def test_packed_equivalence_pallas_backend(smollm):
    """Same differential on the pallas (interpret-mode) backend: decode
    rows run the C2 decode kernel, chunks the Q-Block kernel, inside one
    executable."""
    cfg, params = smollm
    rng = np.random.default_rng(4)
    prompts = H.make_prompts(cfg, rng, (2 * BUDGET + 9, 7))
    padded, packed = _pair(cfg, params, prompts, max_seqs=2,
                           max_model_len=128, backend="pallas",
                           max_new_tokens=4,
                           enable_chunked_prefill=True,
                           max_prefill_tokens=BUDGET)
    H.assert_same_outputs(padded, packed, label_a="padded",
                          label_b="packed")
    assert packed.total("partial_prefills") > 0


def test_debug_launch_copy_replays_on_xla(smollm):
    """Under `debug_logits` the engine keeps a copy of a unified launch's
    cache, since the launch consumed (deleted) the original; replaying
    that copy on the xla executor gives the pallas step's logits on a
    step that packs decode and chunk rows."""
    from repro.serving.executor import SingleDeviceExecutor
    from repro.serving.request import Request
    cfg, params = smollm
    eng = H.build_engine(cfg, params, max_seqs=2, max_model_len=128,
                         backend="pallas", debug_logits=True,
                         enable_chunked_prefill=True,
                         max_prefill_tokens=BUDGET)
    rng = np.random.default_rng(6)
    for p in H.make_prompts(cfg, rng, (5, 2 * BUDGET + 3)):
        eng.add_request(Request(prompt=p, max_new_tokens=4))
    while True:
        assert eng.sched.has_work, "no step mixed decode and chunk rows"
        before = H.pool_leaves(eng)
        stats = eng.step()
        if stats["decode"] and stats["prefill"]:
            break
    assert all(a.is_deleted() for a in before)
    cache_in, batch = eng.last_launch
    xla = SingleDeviceExecutor(
        cfg, backend="xla", max_seqs=eng.max_seqs, fused=True,
        seed=eng.seed, debug_logits=True).build_unified(None)
    _, want, _ = xla(eng.params, cache_in, batch)
    np.testing.assert_allclose(np.asarray(eng.last_step_logits),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


def test_packed_reduces_compile_events_and_padding(smollm):
    """The acceptance observable: on a mixed decode+fresh+resumed trace
    the packed engine compiles FEWER executables (one `unified` family vs
    decode x prefill x prefill_cached buckets) and launches FEWER token
    rows (no [B, S] padding)."""
    cfg, params = smollm
    rng = np.random.default_rng(5)
    prompts = H.make_prompts(cfg, rng, (40, 9, 33, 25, 6, 30))
    padded, packed = _pair(cfg, params, prompts, max_new_tokens=8,
                           enable_chunked_prefill=True,
                           max_prefill_tokens=BUDGET)
    H.assert_same_outputs(padded, packed, label_a="padded",
                          label_b="packed")
    assert all(e[0].startswith("unified") for e in
               packed.engine.compile_events)
    assert len(packed.engine.compile_events) \
        < len(padded.engine.compile_events)
    assert packed.engine.launched_token_slots \
        < padded.engine.launched_token_slots
    # scheduled work is identical, so the slot gap is pure padding waste
    assert packed.total("prefill_tokens") == padded.total("prefill_tokens")


def test_packed_dispatch_uses_unified_tree(smollm):
    """Kernel-config dispatch flows through the unified tree: a loaded
    `unified_tree` steers the packed launch's variant by the packed-mix
    profile (decode-only steps -> segmented, prefill-carrying steps ->
    gqa), each captured once per config."""
    import json
    import os
    import tempfile
    cfg, params = smollm
    rng = np.random.default_rng(6)
    seg = {"variant": "segmented", "tile": None, "num_segments": 4,
           "block_q": 16}
    gqa = {"variant": "gqa", "tile": None, "num_segments": 8, "block_q": 16}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tree.json")
        with open(path, "w") as f:
            json.dump({"decode_tree": [[{}, gqa]],
                       "prefill_tree": [[{}, gqa]],
                       "unified_tree": [
                           [{"decode_share_ge": 0.999}, seg],
                           [{}, gqa]]}, f)
        heuristics.load(path)
        try:
            eng = H.build_engine(cfg, params)
            run = H.run_requests(eng, H.make_prompts(cfg, rng, (9, 17)),
                                 max_new_tokens=8)
            assert eng.dispatch_counts[("unified", "gqa")] > 0
            assert eng.dispatch_counts[("unified", "segmented")] > 0
            # per-config captures stay bounded: one per (bucket, config)
            events = eng.compile_events
            assert len(events) == len(set(events))
            # decode-only steps picked segmented, mixed steps gqa
            for st in run.step_stats:
                if "unified" not in st["dispatch"]:
                    continue
                want = "segmented" if st["prefill"] == 0 else "gqa"
                assert st["dispatch"]["unified"]["variant"] == want
        finally:
            heuristics.reset()


def test_packed_falls_back_for_unsupported_families():
    """SSM-family engines silently use the padded per-kind path (their
    recurrent state is slot-indexed, not page-addressable per token)."""
    from repro.configs import ARCHS, reduced
    cfg = reduced(ARCHS["xlstm-350m"]).replace(dtype="float32")
    import repro.models.model as M
    import jax
    params = M.init(cfg, jax.random.key(0))
    eng = H.build_engine(cfg, params, max_seqs=2, num_pages=32,
                         max_model_len=64)
    assert not eng._packed
    rng = np.random.default_rng(7)
    run = H.run_requests(eng, H.make_prompts(cfg, rng, (9,)),
                         max_new_tokens=3)
    assert len(run.outputs[0]) == 3
    assert all(not e[0].startswith("unified")
               for e in eng.compile_events)
