"""The Morpheus controller (paper §4.1) as a functional, scan-able machine.

One ``step`` processes one LLC request exactly as Fig. 3/6 describe:

  1. *address separation* routes the request to the conventional LLC or the
     extended LLC (static split, §4.1.1);
  2. for extended-tier requests, the *hit/miss predictor* (double Bloom
     filter, §4.1.2) decides whether to forward the request over the
     interconnect to the owning cache-mode chip or to go straight to the
     backing store (predicted miss — as cheap as a conventional miss);
  3. the extended tier performs the tag lookup / LRU / insert the
     extended-LLC kernel would execute (Algorithm 1), with optional BDI
     compression determining each block's physical footprint (§4.3.1).

Implementation note: the step is *straight-line masked code* — every array
receives exactly one dynamic row update per step (writing the old row back
when the branch is not taken).  ``lax.cond`` over the full state would make
XLA copy the whole cache state per trace element; the masked form lets the
scan update buffers in place (~100x faster on CPU).

Correctness invariant used to merge branches: a predicted miss can never be
an actual hit (Bloom has no false negatives; PERFECT mirrors the lookup;
NONE always forwards), so the extended-tier cases reduce to
``hit -> touch`` and ``~hit -> insert`` with the NoC/latency cost depending
on the prediction.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import address_separation as asep
from . import bloom as bloomlib
from .compression import BLOCK_BYTES, HIGH, LOW
from .energy import PaperGPU
from .tag_store import LRU_MAX_INT


class Predictor(enum.Enum):
    BLOOM = "bloom"       # paper design (§4.1.2)
    NONE = "none"         # ablation: forward everything (Fig. 13 No-Prediction)
    PERFECT = "perfect"   # ablation: oracle (Fig. 13 Perfect-Prediction)


@dataclass(frozen=True)
class MorpheusConfig:
    amap: asep.AddressMap
    conv_ways: int = 32
    ext_ways: int = 32              # logical ways at 128 B (budget = ways*128)
    compression: bool = False
    predictor: Predictor = Predictor.BLOOM
    indirect_mov: bool = False      # §4.3.2 ISA support: faster data access
    costs: PaperGPU = PaperGPU()

    @property
    def ext_enabled(self) -> bool:
        return self.amap.ext_sets > 0

    @property
    def ext_max_ways(self) -> int:
        return self.ext_ways * (BLOCK_BYTES // 32) if self.compression \
            else self.ext_ways

    @property
    def ext_budget_bytes(self) -> int:
        return self.ext_ways * BLOCK_BYTES

    def latencies(self) -> Tuple[float, float, float, float, float]:
        """(conv_hit, conv_miss, ext_hit, ext_miss, pred_miss) in ns."""
        c = self.costs
        ext_hit = c.ext_llc.hit_latency_ns
        ext_miss = c.ext_llc.miss_latency_ns
        if self.indirect_mov:
            # §4.3.2: native Indirect-MOV removes the brx.idx switch (3 insts,
            # 2 branches -> 1 inst) from every data-array access.
            ext_hit -= 40.0
            ext_miss -= 40.0
        if self.compression:
            ext_hit += 10.0  # BDI decompress on the hit path (§4.3.1)
        return (c.conv_llc.hit_latency_ns, c.conv_llc.miss_latency_ns,
                ext_hit, ext_miss, c.predicted_miss_latency_ns)


class Stats(NamedTuple):
    conv_hits: jnp.ndarray       # int32 counters
    conv_misses: jnp.ndarray
    ext_hits: jnp.ndarray
    ext_false_pos: jnp.ndarray   # forwarded but actually a miss
    ext_pred_miss: jnp.ndarray   # predicted miss, went straight to DRAM
    ext_true_miss: jnp.ndarray
    dram_accesses: jnp.ndarray
    writebacks: jnp.ndarray
    latency_ns: jnp.ndarray      # float32 sums
    energy_nJ: jnp.ndarray
    noc_bytes: jnp.ndarray       # extended-tier interconnect traffic (§7.4)
    conv_bytes: jnp.ndarray
    dram_bytes: jnp.ndarray
    bloom_swaps: jnp.ndarray     # int32


_INT_FIELDS = ("conv_hits", "conv_misses", "ext_hits", "ext_false_pos",
               "ext_pred_miss", "ext_true_miss", "dram_accesses",
               "writebacks", "bloom_swaps")


def _zero_stats() -> Stats:
    vals = {}
    for f in Stats._fields:
        dt = jnp.int32 if f in _INT_FIELDS else jnp.float32
        vals[f] = jnp.zeros((), dt)
    return Stats(**vals)


class MorpheusState(NamedTuple):
    # conventional LLC (hardware-managed, Algorithm-1-equivalent metadata)
    conv_tags: jnp.ndarray    # (conv_sets, conv_ways) uint32
    conv_valid: jnp.ndarray
    conv_dirty: jnp.ndarray
    conv_lru: jnp.ndarray
    # extended LLC (byte-budgeted for compression)
    ext_tags: jnp.ndarray     # (ext_sets, ext_max_ways)
    ext_valid: jnp.ndarray
    ext_dirty: jnp.ndarray
    ext_lru: jnp.ndarray
    ext_size: jnp.ndarray     # int32 physical bytes per block
    ext_used: jnp.ndarray     # (ext_sets,) int32
    # predictor
    bf1: jnp.ndarray          # (ext_sets, words) uint32
    bf2: jnp.ndarray
    n_mru: jnp.ndarray        # (ext_sets,) int32
    stats: Stats


# 32-byte Bloom filters (paper §4.1.2 'Cost') — shared by the full-state
# initializer and the engine's per-set rows so the two can never drift
BLOOM_WORDS = 8


def make_state(cfg: MorpheusConfig) -> MorpheusState:
    cs, cw = max(cfg.amap.conv_sets, 1), cfg.conv_ways
    es, ew = max(cfg.amap.ext_sets, 1), cfg.ext_max_ways
    words = BLOOM_WORDS
    return MorpheusState(
        conv_tags=jnp.zeros((cs, cw), jnp.uint32),
        conv_valid=jnp.zeros((cs, cw), jnp.bool_),
        conv_dirty=jnp.zeros((cs, cw), jnp.bool_),
        conv_lru=jnp.zeros((cs, cw), jnp.uint32),
        ext_tags=jnp.zeros((es, ew), jnp.uint32),
        ext_valid=jnp.zeros((es, ew), jnp.bool_),
        ext_dirty=jnp.zeros((es, ew), jnp.bool_),
        ext_lru=jnp.zeros((es, ew), jnp.uint32),
        ext_size=jnp.zeros((es, ew), jnp.int32),
        ext_used=jnp.zeros((es,), jnp.int32),
        bf1=jnp.zeros((es, words), jnp.uint32),
        bf2=jnp.zeros((es, words), jnp.uint32),
        n_mru=jnp.zeros((es,), jnp.int32),
        stats=_zero_stats(),
    )


def _idx(a, i):
    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)


def _upd(a, row, i):
    return jax.lax.dynamic_update_index_in_dim(a, row, i, 0)


# ---------------------------------------------------------------------------
# Pure per-set transition kernels.
#
# All mutable simulator state is keyed by (tier, set) and every request
# touches exactly one set, so the whole simulation decomposes into
# independent per-set state machines.  These kernels are that decomposition:
# each maps (sets' state, one request per set) -> (new state, outcome).
#
# Layout: one COLUMN per set.  Way-indexed leaves are (ways, N), Bloom
# words (words, N), per-set scalars and the request fields (1, N).  Every
# reduction runs over axis 0 with keepdims, so all values stay 2-D with
# sets on the lane axis — the form Mosaic lowers on TPU.  The same code
# serves all three engines: ``step`` (the serial oracle) passes one set
# (N = 1), the jnp engine and the Pallas kernel pass every set of a trace.
# Only int32/bool reductions are used (Mosaic has no unsigned reductions or
# unsigned max), so uint32 LRU counters are decremented as int32.
# ---------------------------------------------------------------------------

class ConvRow(NamedTuple):
    """Conventional-LLC sets: (ways, N) metadata."""
    tags: jnp.ndarray     # uint32
    valid: jnp.ndarray    # bool
    dirty: jnp.ndarray    # bool
    lru: jnp.ndarray      # uint32


class ExtRow(NamedTuple):
    """Extended-LLC sets: (ext_max_ways, N) metadata + predictor filters."""
    tags: jnp.ndarray
    valid: jnp.ndarray
    dirty: jnp.ndarray
    lru: jnp.ndarray
    size: jnp.ndarray     # int32 physical bytes per block
    used: jnp.ndarray     # (1, N) int32
    bf1: jnp.ndarray      # (words, N) uint32
    bf2: jnp.ndarray
    n_mru: jnp.ndarray    # (1, N) int32


class ConvOutcome(NamedTuple):
    hit: jnp.ndarray       # bool
    evict_wb: jnp.ndarray  # bool — miss evicted a dirty block


class ExtOutcome(NamedTuple):
    hit: jnp.ndarray       # bool
    pred: jnp.ndarray      # bool — predictor said "forward"
    wbs: jnp.ndarray       # int32 — dirty blocks written back on insert
    swap: jnp.ndarray      # bool — Bloom filters swapped this access


def _any(mask: jnp.ndarray) -> jnp.ndarray:
    """OR over the ways axis, as an int32 max (1, N)."""
    return jnp.max(mask.astype(jnp.int32), axis=0, keepdims=True) > 0


def _first(mask: jnp.ndarray) -> jnp.ndarray:
    """First True way per column, 0 when there is none (= ``argmax``)."""
    w = mask.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 0)
    first = jnp.min(jnp.where(mask, iota, w), axis=0, keepdims=True)
    return jnp.where(first == w, 0, first)


def _argmin(key: jnp.ndarray) -> jnp.ndarray:
    """First minimal way per column of an int32 key (= ``argmin``)."""
    return _first(key == jnp.min(key, axis=0, keepdims=True))


def _sel(c: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``jnp.where`` that Mosaic lowers for bool operands too (it has no
    select over bool vectors, so those become and/or)."""
    if jnp.result_type(x) == jnp.bool_:
        return (c & x) | (~c & y)
    return jnp.where(c, x, y)


def _dec(lru: jnp.ndarray) -> jnp.ndarray:
    """Saturating LRU decrement, in int32."""
    return jnp.maximum(lru, 1) - 1


def conv_set_kernel(cfg: MorpheusConfig, row: ConvRow, tag: jnp.ndarray,
                    is_write: jnp.ndarray) -> Tuple[ConvRow, ConvOutcome]:
    """LRU lookup/insert on conventional sets (Algorithm-1 metadata).
    ``tag`` uint32 and ``is_write`` bool are (1, N)."""
    ctags, cvalid, cdirty, clru = row
    lru = clru.astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, ctags.shape, 0)
    cmatch = cvalid & (ctags == tag)
    c_hit = _any(cmatch)
    way_vic = _argmin(jnp.where(cvalid, lru, -1))
    way = jnp.where(c_hit, _first(cmatch), way_vic)
    onehot = iota == way
    c_evict_wb = ~c_hit & _any((iota == way_vic) & cvalid & cdirty)
    n_ctags = jnp.where(onehot & ~c_hit, tag, ctags)
    n_cvalid = cvalid | (onehot & ~c_hit)
    n_cdirty = _sel(onehot, (c_hit & cdirty) | is_write, cdirty)
    n_clru = jnp.where(onehot, LRU_MAX_INT, _dec(lru)).astype(clru.dtype)
    return (ConvRow(n_ctags, n_cvalid, n_cdirty, n_clru),
            ConvOutcome(c_hit, c_evict_wb))


def ext_set_kernel(cfg: MorpheusConfig, row: ExtRow, tag: jnp.ndarray,
                   is_write: jnp.ndarray, level: jnp.ndarray
                   ) -> Tuple[ExtRow, ExtOutcome]:
    """Predict -> lookup -> touch/insert on extended sets (§4.1-§4.3).
    ``tag`` uint32, ``is_write`` bool and ``level`` int32 are (1, N)."""
    etags, evalid, edirty = row.tags, row.valid, row.dirty
    esize, eused = row.size, row.used
    bf1, bf2, n = row.bf1, row.bf2, row.n_mru
    lru = row.lru.astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, etags.shape, 0)

    ematch = evalid & (etags == tag)
    e_hit = _any(ematch)
    e_way = _first(ematch)

    bits = bloomlib._hash_bits(tag, bf1.shape[0] * 32)
    if cfg.predictor is Predictor.BLOOM:
        pred = bloomlib._test(bf1, bits)
    elif cfg.predictor is Predictor.PERFECT:
        pred = e_hit
    else:
        pred = jnp.ones_like(e_hit)

    if cfg.compression:
        phys = jnp.where(level == HIGH, 32,
                         jnp.where(level == LOW, 64, BLOCK_BYTES))
    else:
        phys = jnp.full(level.shape, BLOCK_BYTES, jnp.int32)

    # touch path (hit): Algorithm 1 lines 8-12
    t_onehot = iota == e_way
    t_lru = jnp.where(t_onehot, LRU_MAX_INT, _dec(lru))
    t_dirty = edirty | (t_onehot & is_write)

    # insert path (miss): LRU-evict until the block fits (≤4 evictions)
    i_valid, i_dirty, i_size, i_used = evalid, edirty, esize, eused
    wbs = jnp.zeros_like(eused)
    budget = cfg.ext_budget_bytes
    for _ in range(BLOCK_BYTES // 32):
        need = (i_used + phys) > budget
        v = _argmin(jnp.where(i_valid, lru, LRU_MAX_INT + 1))
        can = need & _any(i_valid)
        oh = iota == v
        gone = can & oh
        wbs += (can & _any(oh & i_dirty)).astype(jnp.int32)
        i_used = i_used - jnp.sum(jnp.where(gone, i_size, 0), axis=0,
                                  keepdims=True)
        i_valid = i_valid & ~gone
        i_dirty = i_dirty & ~gone
        i_size = jnp.where(gone, 0, i_size)
    oh = iota == _first(~i_valid)
    i_tags = jnp.where(oh, tag, etags)
    i_valid = i_valid | oh
    i_dirty = _sel(oh, is_write, i_dirty)
    i_size = jnp.where(oh, phys, i_size)
    i_lru = jnp.where(oh, LRU_MAX_INT, _dec(lru))
    i_used = i_used + phys

    # merge: hit -> touch rows; miss -> insert rows
    n_etags = jnp.where(e_hit, etags, i_tags)
    n_evalid = _sel(e_hit, evalid, i_valid)
    n_edirty = _sel(e_hit, t_dirty, i_dirty)
    n_elru = jnp.where(e_hit, t_lru, i_lru).astype(row.lru.dtype)
    n_esize = jnp.where(e_hit, esize, i_size)
    n_eused = jnp.where(e_hit, eused, i_used)

    # Bloom maintenance (Fig. 6(b)): every ext access inserts into both
    # filters; n += (tag not already in BF2); swap at n >= associativity.
    if cfg.predictor is Predictor.BLOOM:
        mask = bloomlib._bit_mask(bits, bf1.shape)
        was_in_bf2 = bloomlib._test(bf2, bits)
        u_bf1, u_bf2 = bf1 | mask, bf2 | mask
        u_n = n + jnp.where(was_in_bf2, 0, 1)
        do_swap = u_n >= cfg.ext_ways    # logical associativity
        n_bf1 = jnp.where(do_swap, u_bf2, u_bf1)
        n_bf2 = jnp.where(do_swap, jnp.zeros_like(u_bf2), u_bf2)
        u_n = jnp.where(do_swap, 0, u_n)
    else:
        n_bf1, n_bf2, u_n = bf1, bf2, n
        do_swap = jnp.zeros_like(e_hit)

    return (ExtRow(n_etags, n_evalid, n_edirty, n_elru, n_esize, n_eused,
                   n_bf1, n_bf2, u_n),
            ExtOutcome(e_hit, pred, wbs, do_swap))


def conv_slot(cfg: MorpheusConfig, row: ConvRow, tag, is_write, active,
              counted) -> Tuple[ConvRow, "Stats"]:
    """One request slot of every conventional set: transition the sets
    whose slot is ``active`` (padding holds state) and return the Stats
    delta of the ``counted`` ones.  Request fields are (1, N)."""
    new_row, out = conv_set_kernel(cfg, row, tag, is_write)
    row = jax.tree.map(lambda nn, oo: _sel(active, nn, oo), new_row, row)
    return row, request_stats(cfg, counted, out, np.bool_(False), _NO_EXT)


def ext_slot(cfg: MorpheusConfig, row: ExtRow, tag, is_write, level, active,
             counted) -> Tuple[ExtRow, "Stats"]:
    """``conv_slot`` for the extended tier."""
    new_row, out = ext_set_kernel(cfg, row, tag, is_write, level)
    row = jax.tree.map(lambda nn, oo: _sel(active, nn, oo), new_row, row)
    return row, request_stats(cfg, np.bool_(False), _NO_CONV, counted, out)


def request_stats(cfg: MorpheusConfig, sel_c: jnp.ndarray,
                  conv: ConvOutcome, is_ext: jnp.ndarray, ext: ExtOutcome
                  ) -> Stats:
    """Per-request Stats delta (the §7 metrics of one request).

    ``sel_c``/``is_ext`` gate the conventional/extended contributions; the
    serial ``step`` passes complementary masks, the set-parallel engine
    passes each kernel's activity mask with the other side held False.
    """
    c = cfg.costs
    lat_ch, lat_cm, lat_eh, lat_em, lat_pm = cfg.latencies()
    e_conv = BLOCK_BYTES * c.conv_llc.energy_pJ_per_B * 1e-3   # nJ
    e_ext = BLOCK_BYTES * c.ext_llc.energy_pJ_per_B * 1e-3
    e_dram = BLOCK_BYTES * c.dram.energy_pJ_per_B * 1e-3

    i1 = lambda b: b.astype(jnp.int32)
    f1 = lambda b: b.astype(jnp.float32)
    e_hit, pred, wbs = ext.hit, ext.pred, ext.wbs
    ext_hit_e = is_ext & e_hit                       # served by ext tier
    ext_fp = is_ext & ~e_hit & pred                  # forwarded, missed
    ext_pm = is_ext & ~pred                          # straight to DRAM
    conv_hit_e = sel_c & conv.hit
    conv_miss_e = sel_c & ~conv.hit
    dram = conv_miss_e | (is_ext & ~e_hit)
    wb = i1(conv_miss_e & conv.evict_wb) + jnp.where(is_ext & ~e_hit, wbs, 0)

    lat = (f1(conv_hit_e) * lat_ch + f1(conv_miss_e) * lat_cm
           + f1(ext_hit_e) * lat_eh + f1(ext_fp) * lat_em + f1(ext_pm) * lat_pm)
    energy = (f1(sel_c) * e_conv                    # conv lookup+data
              + f1(ext_hit_e | ext_fp) * e_ext      # ext lookup+data
              + f1(ext_pm) * e_ext * 0.05           # predictor-only energy
              + f1(dram) * e_dram + f1(wb > 0) * wb * e_dram)
    # Extra interconnect traffic of the extended tier: one 128 B data leg
    # per lookup that reaches a cache-mode core (reply on hit, fp probe),
    # one per insert payload, plus dirty writebacks leaving the core.
    # Predicted misses cost nothing extra (Fig. 5: same path as a
    # conventional miss); request headers are folded into the measured
    # per-core ext bandwidth (34 GB/s is end-to-end for 128 B blocks).
    noc = (i1(ext_hit_e | ext_fp) + i1(is_ext & ~e_hit)
           + jnp.where(is_ext & ~e_hit, wbs, 0)) * BLOCK_BYTES

    use_bloom = is_ext & np.bool_(cfg.predictor is Predictor.BLOOM)
    return Stats(
        conv_hits=i1(conv_hit_e),
        conv_misses=i1(conv_miss_e),
        ext_hits=i1(ext_hit_e),
        ext_false_pos=i1(ext_fp),
        ext_pred_miss=i1(ext_pm),
        ext_true_miss=i1(is_ext & ~e_hit),
        dram_accesses=i1(dram),
        writebacks=wb,
        latency_ns=lat,
        energy_nJ=energy,
        noc_bytes=f1(noc),
        conv_bytes=f1(sel_c) * BLOCK_BYTES,
        dram_bytes=f1(dram) * BLOCK_BYTES + f1(wb > 0) * wb * BLOCK_BYTES,
        bloom_swaps=i1(use_bloom & ext.swap),
    )


# numpy scalars (jaxpr literals) rather than jnp arrays so the engine's
# Pallas backend can close over these no-op outcomes inside kernel bodies
_NO_CONV = ConvOutcome(hit=np.bool_(False), evict_wb=np.bool_(False))
_NO_EXT = ExtOutcome(hit=np.bool_(False), pred=np.bool_(False),
                     wbs=np.int32(0), swap=np.bool_(False))


def _one_set(kernel, cfg: MorpheusConfig, row, *req):
    """Apply a column kernel to ONE set: (ways,) rows and scalar request
    fields travel as a single (ways, 1) / (1, 1) column."""
    col = lambda x: jnp.reshape(x, (-1, 1))
    new, out = kernel(cfg, jax.tree.map(col, row), *map(col, req))
    return (jax.tree.map(lambda nn, oo: jnp.reshape(nn, oo.shape), new, row),
            jax.tree.map(lambda x: jnp.reshape(x, ()), out))


def step(cfg: MorpheusConfig, st: MorpheusState,
         addr: jnp.ndarray, is_write: jnp.ndarray, level: jnp.ndarray
         ) -> MorpheusState:
    """Process one LLC request.  ``level`` is the block's BDI level (from
    data contents in the real system; from the trace generator in the sim).

    Thin wrapper over the per-set kernels: route the request, apply the
    kernel to the routed set's rows, write the rows back (masked so the
    untouched tier's state is bit-identical)."""
    tier, local_set = asep.route(cfg.amap, addr)
    tag = asep.tag_of(cfg.amap, addr)
    is_ext = jnp.bool_(cfg.ext_enabled) & (tier == asep.EXTENDED)
    conv_set = jnp.where(is_ext, 0, local_set)
    ext_set = jnp.where(is_ext, local_set, 0)
    sel_c = ~is_ext
    is_write = jnp.asarray(is_write, jnp.bool_)

    # ----- conventional LLC row update (identity when routed extended) -----
    crow = ConvRow(_idx(st.conv_tags, conv_set), _idx(st.conv_valid, conv_set),
                   _idx(st.conv_dirty, conv_set), _idx(st.conv_lru, conv_set))
    n_crow, c_out = _one_set(conv_set_kernel, cfg, crow, tag, is_write)
    st = st._replace(
        conv_tags=_upd(st.conv_tags, jnp.where(sel_c, n_crow.tags, crow.tags),
                       conv_set),
        conv_valid=_upd(st.conv_valid,
                        jnp.where(sel_c, n_crow.valid, crow.valid), conv_set),
        conv_dirty=_upd(st.conv_dirty,
                        jnp.where(sel_c, n_crow.dirty, crow.dirty), conv_set),
        conv_lru=_upd(st.conv_lru, jnp.where(sel_c, n_crow.lru, crow.lru),
                      conv_set),
    )

    # ----- extended tier: predict -> lookup -> touch/insert ----------------
    erow = ExtRow(_idx(st.ext_tags, ext_set), _idx(st.ext_valid, ext_set),
                  _idx(st.ext_dirty, ext_set), _idx(st.ext_lru, ext_set),
                  _idx(st.ext_size, ext_set), _idx(st.ext_used, ext_set),
                  _idx(st.bf1, ext_set), _idx(st.bf2, ext_set),
                  _idx(st.n_mru, ext_set))
    n_erow, e_out = _one_set(ext_set_kernel, cfg, erow, tag, is_write,
                             level)
    st = st._replace(
        ext_tags=_upd(st.ext_tags, jnp.where(is_ext, n_erow.tags, erow.tags),
                      ext_set),
        ext_valid=_upd(st.ext_valid,
                       jnp.where(is_ext, n_erow.valid, erow.valid), ext_set),
        ext_dirty=_upd(st.ext_dirty,
                       jnp.where(is_ext, n_erow.dirty, erow.dirty), ext_set),
        ext_lru=_upd(st.ext_lru, jnp.where(is_ext, n_erow.lru, erow.lru),
                     ext_set),
        ext_size=_upd(st.ext_size, jnp.where(is_ext, n_erow.size, erow.size),
                      ext_set),
        ext_used=_upd(st.ext_used, jnp.where(is_ext, n_erow.used, erow.used),
                      ext_set),
        bf1=_upd(st.bf1, jnp.where(is_ext, n_erow.bf1, erow.bf1), ext_set),
        bf2=_upd(st.bf2, jnp.where(is_ext, n_erow.bf2, erow.bf2), ext_set),
        n_mru=_upd(st.n_mru, jnp.where(is_ext, n_erow.n_mru, erow.n_mru),
                   ext_set),
    )

    delta = request_stats(cfg, sel_c, c_out, is_ext, e_out)
    return st._replace(stats=jax.tree.map(jnp.add, st.stats, delta))


def simulate(cfg: MorpheusConfig, addrs: jnp.ndarray, writes: jnp.ndarray,
             levels: jnp.ndarray, warmup: int = 0) -> Stats:
    """Replay a request trace through the controller via ``lax.scan``.

    The first ``warmup`` accesses update cache/predictor state but are
    excluded from the returned stats (cold/compulsory misses would
    otherwise dominate short traces and mask steady-state behaviour)."""
    init = make_state(cfg)
    zeros = _zero_stats()

    def body(st, req):
        a, w, l, i = req
        st = step(cfg, st, a, w, l)
        if warmup:
            stats = jax.tree.map(
                lambda s, z: jnp.where(i < warmup, z, s), st.stats, zeros)
            st = st._replace(stats=stats)
        return st, ()

    n = addrs.shape[0]
    final, _ = jax.lax.scan(body, init, (addrs.astype(jnp.uint32),
                                         writes.astype(jnp.bool_),
                                         levels.astype(jnp.int32),
                                         jnp.arange(n, dtype=jnp.int32)))
    return final.stats


simulate_jit = jax.jit(simulate, static_argnums=(0, 4))
