"""Double-Bloom-filter hit/miss predictor (paper §4.1.2, Fig. 6).

The predictor keeps, per extended-LLC set, two Bloom filters:

* ``BF1`` — invariant (1): contains *at least* all cache blocks currently
  resident in the set.  Querying BF1 therefore never produces a false
  negative, which the paper shows is required for correctness (a false
  negative would serve stale data from the backing store).
* ``BF2`` — invariant (2): contains the ``n`` most-recently-used blocks of
  the set.  Once ``n >= associativity``, LRU replacement guarantees every
  resident block is among the ``n`` MRU blocks, so BF2 also satisfies
  invariant (1) while containing fewer stale (evicted) blocks.  At that
  point BF1 is discarded, BF2 becomes the new BF1, and an empty filter
  starts collecting as the new BF2 ("clear, swap, repeat", paper Fig. 6 (9)).

Everything is stored as flat JAX arrays so the predictor state for *all*
sets is one pytree; every operation is jittable and is O(set) via dynamic
indexing (no full-table scans), which is what lets the trace simulator run
as a ``lax.scan``.

Bit layout: each filter is ``words_per_filter`` uint32 words (paper: 32 B
per filter = 8 words).  ``NUM_HASHES`` independent multiply-shift hashes
set/test ``NUM_HASHES`` bits per element.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

# Multiply-shift hash constants (large odd 32-bit multipliers).  Distinct
# per hash function; fixed so behaviour is reproducible.
_HASH_MULTIPLIERS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)
NUM_HASHES = 3  # paper-scale filters (32 B) work well with k=3


class BloomPredictorState(NamedTuple):
    """Predictor state for ``num_sets`` extended-LLC sets."""

    bf1: jnp.ndarray        # (num_sets, words) uint32 — prediction filter
    bf2: jnp.ndarray        # (num_sets, words) uint32 — MRU collector
    n_mru: jnp.ndarray      # (num_sets,) int32 — paper's ``n`` per set
    associativity: jnp.ndarray  # () int32 — swap threshold
    # statistics (monotone counters)
    queries: jnp.ndarray            # () int32
    predicted_hits: jnp.ndarray     # () int32
    swaps: jnp.ndarray              # () int32


def make_state(num_sets: int, associativity: int, *, filter_bytes: int = 32) -> BloomPredictorState:
    words = filter_bytes // 4
    if words < 1:
        raise ValueError("filter_bytes must be >= 4")
    zeros = jnp.zeros((num_sets, words), dtype=jnp.uint32)
    return BloomPredictorState(
        bf1=zeros,
        bf2=zeros,
        n_mru=jnp.zeros((num_sets,), dtype=jnp.int32),
        associativity=jnp.asarray(associativity, dtype=jnp.int32),
        queries=jnp.zeros((), dtype=jnp.int32),
        predicted_hits=jnp.zeros((), dtype=jnp.int32),
        swaps=jnp.zeros((), dtype=jnp.int32),
    )


def _hash_bits(tag: jnp.ndarray, num_bits: int) -> Tuple[jnp.ndarray, ...]:
    """Return the NUM_HASHES bit positions (int32, < num_bits) for ``tag``,
    one array of ``tag``'s shape per hash.

    Unrolled over the (static, tiny) multiplier list with scalar constants
    only — no captured constant vectors and no stacking — so the same code
    is traceable both under jit/vmap and inside Pallas kernel bodies.
    """
    tag = tag.astype(jnp.uint32)
    out = []
    for m in _HASH_MULTIPLIERS[:NUM_HASHES]:
        # multiply-shift: high bits of tag * odd constant are well mixed
        hm = tag * jnp.uint32(m)
        h = hm ^ (hm >> jnp.uint32(15))
        out.append((h % jnp.uint32(num_bits)).astype(jnp.int32))
    return tuple(out)


def _bit_mask(bits: Tuple[jnp.ndarray, ...], shape) -> jnp.ndarray:
    """Expand bit positions into a uint32 OR-mask of ``shape`` (words on
    axis 0)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    masks = jnp.zeros(shape, jnp.uint32)
    for b in bits:
        one = jnp.uint32(1) << (b & 31).astype(jnp.uint32)
        masks = masks | jnp.where(iota == (b >> 5), one, jnp.uint32(0))
    return masks


def _test(filter_words: jnp.ndarray, bits: Tuple[jnp.ndarray, ...]
          ) -> jnp.ndarray:
    """True iff all hash bits are set in the filter (possible membership).
    Words run along axis 0: a (words, N) filter gives a (1, N) answer, a
    (words,) filter a scalar.  The word holding each bit is picked with a
    one-hot over the words and an int32 max (the form Mosaic lowers)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, filter_words.shape, 0)
    present = None
    for b in bits:
        bit = (b & 31).astype(jnp.uint32)
        set_ = (iota == (b >> 5)) & (((filter_words >> bit) & 1) == 1)
        has = jnp.max(set_.astype(jnp.int32), axis=0,
                      keepdims=filter_words.ndim > 1) > 0
        present = has if present is None else present & has
    return present


def predict(state: BloomPredictorState, set_idx: jnp.ndarray, tag: jnp.ndarray
            ) -> Tuple[jnp.ndarray, BloomPredictorState]:
    """Paper Fig. 6(a): query BF1 — predicted hit iff tag maybe-in-BF1.

    Zero false negatives by invariant (1).
    """
    words = state.bf1.shape[1]
    bits = _hash_bits(tag, words * 32)
    row = jax.lax.dynamic_index_in_dim(state.bf1, set_idx, axis=0, keepdims=False)
    hit = _test(row, bits)
    new_state = state._replace(
        queries=state.queries + 1,
        predicted_hits=state.predicted_hits + hit.astype(jnp.int32),
    )
    return hit, new_state


def record_access(state: BloomPredictorState, set_idx: jnp.ndarray, tag: jnp.ndarray
                  ) -> BloomPredictorState:
    """Paper Fig. 6(b): on every extended-LLC access (insert or reuse, (5)/(6)),
    insert the tag into both filters (7); bump ``n`` if the tag was not
    already in BF2; swap when ``n >= associativity`` (8)-(9)."""
    words = state.bf1.shape[1]
    bits = _hash_bits(tag, words * 32)
    mask = _bit_mask(bits, (words,))

    bf1_row = jax.lax.dynamic_index_in_dim(state.bf1, set_idx, 0, keepdims=False)
    bf2_row = jax.lax.dynamic_index_in_dim(state.bf2, set_idx, 0, keepdims=False)
    was_in_bf2 = _test(bf2_row, bits)

    bf1_row = bf1_row | mask
    bf2_row = bf2_row | mask
    n = jax.lax.dynamic_index_in_dim(state.n_mru, set_idx, 0, keepdims=False)
    n = n + jnp.where(was_in_bf2, 0, 1).astype(jnp.int32)

    do_swap = n >= state.associativity
    # swap: new BF1 <- BF2 (still contains this access), new BF2 <- empty, n <- 0
    new_bf1_row = jnp.where(do_swap, bf2_row, bf1_row)
    new_bf2_row = jnp.where(do_swap, jnp.zeros_like(bf2_row), bf2_row)
    new_n = jnp.where(do_swap, 0, n)

    return state._replace(
        bf1=jax.lax.dynamic_update_index_in_dim(state.bf1, new_bf1_row, set_idx, 0),
        bf2=jax.lax.dynamic_update_index_in_dim(state.bf2, new_bf2_row, set_idx, 0),
        n_mru=jax.lax.dynamic_update_index_in_dim(state.n_mru, new_n, set_idx, 0),
        swaps=state.swaps + do_swap.astype(jnp.int32),
    )


def false_positive_rate(filter_bytes: int, num_elements: int, num_hashes: int = NUM_HASHES) -> float:
    """Analytic Bloom FP rate (paper sizing sanity check: 32 B, assoc≈32)."""
    import math
    m = filter_bytes * 8
    k = num_hashes
    n = max(num_elements, 1)
    return (1.0 - math.exp(-k * n / m)) ** k


# --------------------------------------------------------------------------
# Counting Bloom filter — the paper's footnote-2 alternative
# --------------------------------------------------------------------------
# "Counting Bloom filters [30] would support individual element removal
#  instead, but require more bits compared to standard Bloom filters."
# We implement it so the trade-off is measurable (see
# benchmarks? -> tests/test_bloom.py ablation + §Perf notes): with
# per-element REMOVAL on eviction the filter tracks residency exactly
# (modulo counter saturation), so it needs no BF2/swap machinery — at
# 4 bits per counter it costs 4x the storage of a plain filter with the
# same number of cells.

class CountingBloomState(NamedTuple):
    counters: jnp.ndarray   # (num_sets, cells) uint8, saturating at 15
    cells: jnp.ndarray      # () int32


def make_counting_state(num_sets: int, *, filter_bytes: int = 32
                        ) -> CountingBloomState:
    """``filter_bytes`` of 4-bit counters -> 2 cells per byte.  To compare
    like-for-like with the standard filter at equal FP rate, give the
    counting filter 4x the bytes (same cell count)."""
    cells = filter_bytes * 2
    return CountingBloomState(
        counters=jnp.zeros((num_sets, cells), dtype=jnp.uint8),
        cells=jnp.asarray(cells, jnp.int32))


def _counting_cells(tag: jnp.ndarray, cells: int) -> jnp.ndarray:
    return _hash_bits(tag, cells)          # reuse the k multiply-shift hashes


def counting_insert(st: CountingBloomState, set_idx, tag) -> CountingBloomState:
    row = st.counters[set_idx]
    for i in _counting_cells(tag, row.shape[-1]):
        c = row[i]
        row = row.at[i].set(jnp.minimum(c + 1, 15).astype(jnp.uint8))
    return st._replace(counters=st.counters.at[set_idx].set(row))


def counting_remove(st: CountingBloomState, set_idx, tag) -> CountingBloomState:
    """Element removal on eviction — the capability plain filters lack.
    Saturated counters (15) are sticky: decrementing them could create
    false negatives, so they stay (a standard counting-BF rule)."""
    row = st.counters[set_idx]
    for i in _counting_cells(tag, row.shape[-1]):
        c = row[i]
        dec = jnp.where((c > 0) & (c < 15), c - 1, c)
        row = row.at[i].set(dec.astype(jnp.uint8))
    return st._replace(counters=st.counters.at[set_idx].set(row))


def counting_query(st: CountingBloomState, set_idx, tag) -> jnp.ndarray:
    row = st.counters[set_idx]
    hit = jnp.bool_(True)
    for i in _counting_cells(tag, row.shape[-1]):
        hit &= row[i] > 0
    return hit
