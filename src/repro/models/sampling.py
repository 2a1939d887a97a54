"""In-graph token sampling (greedy / temperature / top-k / top-p) with
per-request counter-based PRNG streams.

This is the sampling half of the fused packed launch
(`models.model.apply_unified(..., sample=True)`) AND the retained
two-dispatch `Engine._sample_fn` — one definition, so the packed, padded,
and fused paths are bit-identical by construction (docs/serving.md).

The contract:

  1. **Greedy rows** (`temperature <= 0`) return `argmax(logits)`.  The
     temperature divisor is clamped to 1.0 for them — never the historical
     `max(t, 1e-6)`, whose x1e6 blow-up overflows/NaNs large or
     `-inf`-masked logits on the discarded branch of the
     `where(temperature > 0, ...)` select.
  2. **Sampled rows** scale by temperature, then apply top-k (keep the k
     highest logits; `k <= 0` disables), then top-p (the smallest
     descending-probability prefix whose mass reaches p; `p >= 1`
     disables), then draw from the renormalized survivors.  Boundary ties
     are all kept (both filters threshold on the logit value), and the
     top-1 always survives.
  3. **Randomness is a pure function of
     (engine seed, request stream id, tokens generated so far)**:
     `key = fold_in(fold_in(key(seed), stream), n_generated)`.  There is
     no launch-wide key, so a request's drawn tokens cannot depend on
     batch composition, slot or row placement, dead decode rows, or which
     engine path (packed / padded / solo) executed it — the RNG
     reproducibility guarantee the sampling-equivalence suite pins.
  4. **Thresholds by bisection, never a sort.**  Each filter keeps
     `x >= cut` for one cut per row.  The cut is found by bisection over
     the order-preserving int32 image of the float32 values
     (`_order_keys`): 32 passes, each a compare and a row reduction — a
     count for top-k (the least key c with `count(x > c) < k`), the row's
     float32 softmax mass for top-p (the least c with `mass(x > c) < p`).
     The cut is a value of the row, the one the descending-sort
     definition picks: exactly for top-k, and for top-p up to the float32
     rounding of the mass, as the sort's cumulative sum was.  There is no
     cap on candidates, so a nucleus as wide as the vocabulary is found
     whole.
  5. **Only the work the rows ask for** (`filter_rows`).  The top-k search
     runs only if some sampled row has `top_k > 0`, the top-p search only
     if some sampled row has `top_p < 1`, and the categorical draw only if
     some row samples (`lax.cond` on the batch's own parameters); an
     all-greedy batch is one argmax.  Greedy rows never trip a branch:
     their filter parameters are neutralised first.  The engine counts
     the same predicates on the host (packed `step()["sampler"]`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# An int32 key interval (lo, hi] of up to 2**32 keys halves to one key in
# 32 steps.
_BISECT_STEPS = 32
_NO_CUT = jnp.iinfo(jnp.int32).min  # every key is >= it


def request_keys(seed: int, stream_ids, num_generated):
    """Per-row PRNG keys from the engine seed and each row's
    (stream id, tokens-generated-so-far) counters — both int32 [S]."""
    base = jax.random.key(seed)

    def derive(stream, n):
        return jax.random.fold_in(jax.random.fold_in(base, stream), n)

    return jax.vmap(derive)(stream_ids, num_generated)


def filter_rows(temperature, top_p, top_k):
    """Boolean masks `(sampled, top_p rows, top_k rows)`: which rows draw,
    and which of those need each search.  Plain operators, so the sampler's
    branches (jax arrays) and the engine's counters (numpy) share them."""
    sampled = temperature > 0
    return sampled, sampled & (top_p < 1.0), sampled & (top_k > 0)


def scaled_logits(logits, temperature):
    """Temperature scaling with the greedy divisor clamped to 1.0:
    `temperature <= 0` rows pass through UNCHANGED (their argmax is taken
    later), instead of being multiplied by up to 1e6 on a dead branch."""
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    return logits.astype(jnp.float32) / safe_t[:, None]


def _order_keys(x):
    """int32 keys in the order of the float32 values `x`: sign-magnitude
    bits to two's complement, so -0.0 and 0.0 share a key as they compare
    equal (NaN is not ordered)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, -(bits & 0x7FFFFFFF), bits)


def _least_cut(values, holds):
    """Per row, the least key c for which `holds(x, key(x) > c)` is true,
    x = `values()` [S, V], `holds` monotone in c: bisection over
    (min key - 1, max key], assumed false at the low end and true at the
    high one.  What `holds` sums only changes at a value of the row, so
    the cut is one.  Each pass recomputes x from the raw logits, so
    nothing [S, V] is stored."""
    def halve(_, bounds):
        lo, hi = bounds
        # floor((lo + hi) / 2) without the int32 overflow of lo + hi
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        x = values()
        ok = holds(x, _order_keys(x) > mid[:, None])
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    x = values()
    bounds = (_order_keys(jnp.min(x, axis=-1)) - 1,
              _order_keys(jnp.max(x, axis=-1)))
    return lax.fori_loop(0, _BISECT_STEPS, halve, bounds)[1]


def _keep(x, cut):
    """`x` where its key is at least the row's cut, else -inf."""
    return jnp.where(_order_keys(x) >= cut[:, None], x, -jnp.inf)


def _when_any(on, cut):
    """`cut()` on the rows in `on`, run only if some row is; _NO_CUT on
    the rest.  The barrier keeps XLA from sinking the [S, V] broadcast
    that consumes the cut into the cond's branches."""
    found = lambda: jnp.where(on, cut(), _NO_CUT)  # noqa: E731
    none = lambda: jnp.full(on.shape, _NO_CUT)  # noqa: E731
    return lax.optimization_barrier(lax.cond(jnp.any(on), found, none))


def _cut(logits, temperature, top_p, top_k):
    """Per-row key cut of scale -> top-k -> top-p.  The scaled (and
    top-k-kept) rows are recomputed from `logits` wherever they are read,
    never stored."""
    scaled = lambda: scaled_logits(logits, temperature)  # noqa: E731
    # top-k: the k-th highest value is the least key with fewer than k
    # values above it (for k >= V that holds down to the bottom of the
    # row, and every value is kept)
    k_on = top_k > 0
    k_cut = _when_any(k_on, lambda: _least_cut(
        scaled, lambda x, above: jnp.sum(above, axis=-1) < top_k))
    # top-p over the top-k survivors: the least value whose strictly-greater
    # mass is below p, in the survivors' float32 softmax (max and sum taken
    # once).  Near p = 1 the mass at or below the cut is the small, exact
    # side of the comparison, so it is the side compared there.
    kept = lambda: _keep(scaled(), k_cut)  # noqa: E731

    def nucleus():
        m = jnp.max(kept(), axis=-1, keepdims=True)
        z = jnp.sum(jnp.exp(kept() - m), axis=-1)

        def holds(x, above):
            e = jnp.exp(x - m)
            head = jnp.sum(jnp.where(above, e, 0.0), axis=-1)
            tail = jnp.sum(jnp.where(above, 0.0, e), axis=-1)
            return jnp.where(top_p > 0.5, tail > (1.0 - top_p) * z,
                             head < top_p * z)

        return _least_cut(kept, holds)

    return jnp.maximum(k_cut, _when_any(top_p < 1.0, nucleus))


def filter_logits(logits, temperature, top_p, top_k):
    """The full pre-draw transform (scale -> top-k -> top-p, filtered
    entries -inf), exposed so the numpy-reference tests can compare
    kept-token sets without RNG.  Each filter applies to the rows whose
    own parameter enables it."""
    return _keep(scaled_logits(logits, temperature),
                 _cut(logits, temperature, top_p, top_k))


def sample_tokens(logits, temperature, top_p, top_k, keys):
    """Sample one token per row of `logits` [S, V].  Greedy rows
    (`temperature <= 0`) take argmax of the RAW logits; sampled rows draw
    categorically from `filter_logits` under that row's own key.  A batch
    with no sampled row skips the filters and the draw."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled, p_rows, k_rows = filter_rows(temperature, top_p, top_k)

    def draw():
        x = filter_logits(logits, temperature, jnp.where(p_rows, top_p, 1.0),
                          jnp.where(k_rows, top_k, 0))
        drawn = jax.vmap(lambda key, row: jax.random.categorical(key, row))(
            keys, x)
        return jnp.where(sampled, drawn, greedy).astype(jnp.int32)

    return lax.cond(jnp.any(sampled), draw, lambda: greedy)
