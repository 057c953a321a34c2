"""Set-parallel batched simulation engine.

``controller.simulate`` replays a trace one request at a time through a
``lax.scan`` — correct, but serial in the trace length.  All mutable
simulator state (tags, valid/dirty bits, LRU counters, byte budgets, Bloom
filters) is keyed by cache set and the Stats are pure per-request sums, so
requests that map to *different* sets commute exactly: the simulation
decomposes into thousands of independent per-set state machines.

This module exploits that:

  1. ``pack`` partitions each trace by (tier, set) on the host — one
     stable sort per trace on its global set index (a 16-bit key at
     every configuration's set count, which numpy sorts by radix), so
     the in-set request order (the only order that matters) is preserved
     and, conventional sets being numbered below extended ones, both
     tiers come out as contiguous runs — and scatters each column once,
     through a flat index, straight into the padded dense (B, num_sets,
     L) batch arrays with an activity mask.
  2. ``_run_packed_state`` scans the packed slots with the pure per-set
     kernels from ``controller`` (the same code the serial oracle runs),
     each step transitioning every set of a trace at once (one column per
     set), ``vmap``-ed over a batch of traces; per-request Stats deltas
     are accumulated in the scan carry and reduced over sets.
     ``_run_packed`` is the same from cold caches.
  3. ``simulate_parallel`` / ``simulate_batch`` are the public entry
     points.  Integer counters are *exactly* equal to the serial scan's
     (same kernels, same in-set order); float sums differ only by
     accumulation order (well inside 1e-3 relative).

Wall-clock: the scan length drops from N (trace length) to the padded
max per-set subsequence length (~N / num_sets), and the per-step work
vectorizes over sets — on CPU this is dominated by scan-iteration
overhead, so the speedup is roughly the scan-length ratio.

Shapes are bucketed (pow2 padding of L) so repeated calls with the same
config reuse one compiled executable across apps, seeds and grid points.

Backends: the inner per-set scan has two interchangeable implementations,
selected by ``backend`` on every public entry point (and threaded through
``cache_sim.RunPoint``/``run_batch``, ``policy`` and the benchmarks):

  * ``"jnp"``    — the pure-jnp all-sets scan below (CPU default);
  * ``"pallas"`` — the fused Pallas kernel in ``kernels/engine_scan.py``
    (default on TPU hosts; interpret mode on the CPU).  Integer Stats
    are bit-identical across backends —
    both apply the same ``controller`` transition kernels in the same
    in-set order (tests/test_engine.py).

``REPRO_ENGINE_BACKEND`` overrides the default; ``resolve_backend`` turns
an unsupported selection into a clear error instead of a Pallas traceback.

Resumable state: the per-set scan's full carry — tags, valid/dirty bits,
LRU counters, byte budgets, Bloom filters, accumulated Stats and stream
position — is also exposed as an explicit ``EngineState`` pytree
(``init_state`` / ``advance_packed``), so a trace can be replayed in
fixed-length epochs with integer Stats bit-identical to one monolithic
run on either backend.  ``runtime/stream.py`` builds the epoch-streaming
runtime on top of this.
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import controller as ctl
from .. import obs
from .controller import MorpheusConfig, Stats

BACKENDS = ("jnp", "pallas")


class BackendError(RuntimeError):
    """Requested engine backend cannot run on this host."""


def backend_status(backend: str) -> Tuple[bool, str]:
    """(supported, human-readable detail) for an engine backend name."""
    if backend == "jnp":
        return True, "pure-jnp all-sets scan"
    if backend == "pallas":
        from ..kernels import engine_scan
        return engine_scan.supported()
    return False, f"unknown backend {backend!r}; choose from {BACKENDS}"


def default_backend() -> str:
    """Session default: env override, else pallas on TPU hosts, else jnp."""
    env = os.environ.get("REPRO_ENGINE_BACKEND", "").strip()
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def resolve_backend(backend: str | None = None) -> str:
    """Validate a backend choice (None -> session default) or raise a
    ``BackendError`` whose message says what to do about it."""
    b = backend or default_backend()
    ok, detail = backend_status(b)
    if not ok:
        raise BackendError(
            f"engine backend {b!r} is unavailable on this host: {detail}. "
            f"Use backend='jnp' (or unset REPRO_ENGINE_BACKEND).")
    return b


class PackedTraces(NamedTuple):
    """A batch of traces partitioned by (tier, set) and padded.

    Leading dims: B traces x S sets x L padded subsequence slots.  A slot
    with ``active == False`` is padding and is a provable no-op in the
    engine (state held, stats delta zero).
    """
    conv_tag: np.ndarray      # (B, Sc, Lc) uint32
    conv_write: np.ndarray    # (B, Sc, Lc) bool
    conv_pos: np.ndarray      # (B, Sc, Lc) int32 — original trace position
    conv_active: np.ndarray   # (B, Sc, Lc) bool
    ext_tag: np.ndarray       # (B, Se, Le) uint32
    ext_write: np.ndarray     # (B, Se, Le) bool
    ext_level: np.ndarray     # (B, Se, Le) int32
    ext_pos: np.ndarray       # (B, Se, Le) int32
    ext_active: np.ndarray    # (B, Se, Le) bool
    warmup: np.ndarray        # (B,) int32


def _bucket(n: int, minimum: int = 16) -> int:
    """Round a padded length up to a power of two (compile-cache friendly)."""
    if n <= minimum:
        return minimum
    return 1 << (int(n) - 1).bit_length()


_UNCOUNTED_POS = np.int32(-(1 << 30))


def pack(cfg: MorpheusConfig,
         traces: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray, int]],
         pos0: Sequence[int] | None = None,
         count: Sequence[np.ndarray | None] | None = None) -> PackedTraces:
    """Partition a batch of (addrs, writes, levels, warmup) traces.

    Traces may have different lengths and warmups; shorter traces simply
    carry more padding.  The config's address map decides the partition.

    ``pos0`` (per-trace, default all-zero) offsets the recorded request
    positions: an epoch stream packs each slice with ``pos0 = epoch
    start`` so the *global* positions — and therefore the ``pos >=
    warmup`` stats mask — are identical to a monolithic pack.

    ``count`` (per-trace boolean mask or None) selects which requests are
    *counted* in the Stats.  Uncounted requests still replay — they update
    tags/LRU/Bloom state exactly like any other request — but their
    position is recorded as a large negative number, so the engines' ``pos
    >= warmup`` stats mask (identical on both backends) excludes them.
    This is how the workload subsystem attributes per-tenant Stats: K
    replays of the same composed stream whose masks partition the
    requests sum to the unmasked run bit-identically on integer counters.

    Each tier's set axis is padded with empty sets (never active) to whole
    tiles of the Pallas scan (``set_tiling``); a tier one tile holds keeps
    its set count.

    Span ``engine.pack``; counter ``packed_slots`` counts the padded
    slots returned, ``B x (Sc x Lc + Se x Le)``: the slots the scan steps
    through, of which the trace's requests fill the active ones; counter
    ``tier_requests{tier="conv"|"ext"}`` the requests packed on each tier.
    """
    with obs.span("engine.pack", traces=len(traces)):
        pt, n_conv, n_ext = _pack(cfg, traces, pos0, count)
    if obs.metrics_on():
        obs.count("packed_slots", pt.conv_tag.size + pt.ext_tag.size)
        obs.count("tier_requests", n_conv, tier="conv")
        obs.count("tier_requests", n_ext, tier="ext")
    return pt


def set_tiling(cfg: MorpheusConfig
               ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """The Pallas scan's (sets per tile, tiles) of the conventional and
    the extended tier, from each tier's state rows per set (``ConvRow``:
    four leaves of ``conv_ways``; ``ExtRow``: five of ``ext_max_ways``,
    two Bloom filters and two scalars) and request columns
    (``kernels.engine_scan.set_tiling``)."""
    from ..kernels import engine_scan
    ext_rows = 5 * cfg.ext_max_ways + 2 * ctl.BLOOM_WORDS + 2
    return (engine_scan.set_tiling(cfg.amap.conv_sets, 4 * cfg.conv_ways, 4),
            engine_scan.set_tiling(cfg.amap.ext_sets, ext_rows, 5))


def _scatter(arrs: Sequence[np.ndarray], i: int, flat: np.ndarray,
             cols: Sequence[np.ndarray]) -> None:
    """Write trace ``i``'s sorted columns of one tier into its batch arrays
    (the last one the activity mask) at flat slots ``flat``."""
    for arr, col in zip(arrs, cols):
        arr[i].reshape(-1)[flat] = col
    arrs[-1][i].reshape(-1)[flat] = True


def _pack(cfg: MorpheusConfig,
          traces: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray, int]],
          pos0: Sequence[int] | None,
          count: Sequence[np.ndarray | None] | None) -> PackedTraces:
    """One stable sort per trace on the global set, for both tiers at once
    (conventional sets are numbered below extended ones, so the sorted
    order is every conventional set's run, then every extended set's),
    then each column scattered once, through a flat index, straight into
    its batch array."""
    amap = cfg.amap
    total = max(amap.total_sets, 1)
    sc, se = amap.conv_sets, amap.ext_sets
    (tc, nc), (te, ne) = set_tiling(cfg)
    sc_pad, se_pad = tc * nc, te * ne
    # the narrowest key that holds every set: 8 or 16 bits sort by radix
    key_dt = np.min_scalar_type(total - 1)
    sorted_ = []
    max_c = max_e = n_req = n_ext = 0
    for addrs, _, _, _ in traces:
        addrs = np.asarray(addrs, np.uint32)
        key = (addrs % np.uint32(total)).astype(key_dt)
        counts = np.bincount(key, minlength=total)
        n_conv = int(counts[:sc].sum()) if se else len(addrs)
        if sc:
            max_c = max(max_c, int(counts[:sc].max()))
        if se:
            max_e = max(max_e, int(counts[sc:].max()))
        n_req += len(addrs)
        n_ext += len(addrs) - n_conv
        sorted_.append((addrs, np.argsort(key, kind="stable"), counts,
                        n_conv))

    lc = _bucket(max_c) if sc and max_c else 0
    le = _bucket(max_e) if se and max_e else 0
    b = len(traces)
    conv = [np.zeros((b, sc_pad, lc), dt) for dt in
            (np.uint32, bool, np.int32, bool)]
    ext = [np.zeros((b, se_pad, le), dt) for dt in
           (np.uint32, bool, np.int32, np.int32, bool)]
    warmups = np.zeros((b,), np.int32)
    # a set's first flat slot in its tier's (sets, L) block
    base = np.concatenate([np.arange(sc) * lc,
                           np.arange(total - sc) * le])
    ks = np.arange(max((len(o) for _, o, _, _ in sorted_), default=0))
    for i, ((addrs, order, counts, n_conv), (_, writes, levels, warmup)) \
            in enumerate(zip(sorted_, traces)):
        warmups[i] = warmup
        # flat slot of the k-th sorted request: its set's base plus its
        # rank within the set, k less the set's first sorted index
        starts = np.cumsum(counts) - counts
        flat = np.repeat(base - starts, counts)
        flat += ks[:len(flat)]
        tag = np.take(addrs, order) // np.uint32(total)
        writes = np.take(np.asarray(writes, bool), order)
        pos = order.astype(np.int32)
        if pos0 is not None:
            pos += int(pos0[i])
        if count is not None and count[i] is not None:
            mask = np.asarray(count[i], bool)
            assert mask.shape == addrs.shape, "count mask length mismatch"
            pos[~np.take(mask, order)] = _UNCOUNTED_POS
        if lc:
            c = slice(0, n_conv)
            _scatter(conv, i, flat[c], (tag[c], writes[c], pos[c]))
        if le:
            e = slice(n_conv, None)
            _scatter(ext, i, flat[e],
                     (tag[e], writes[e],
                      np.take(np.asarray(levels, np.int32), order[e]),
                      pos[e]))
    return (PackedTraces(conv[0], conv[1], conv[2], conv[3],
                         ext[0], ext[1], ext[2], ext[3], ext[4], warmups),
            n_req - n_ext, n_ext)


# ------------------------------------------------------------------ state

class EngineState(NamedTuple):
    """The packed engine's full carry, as an explicit pytree.

    Everything the per-set scan threads between requests, for a batch of B
    traces: the conventional tier's tag-store rows, the extended tier's
    rows + byte budgets + double Bloom filters, the accumulated Stats and
    the stream position.  ``advance_packed`` consumes and returns this, so
    a trace can be replayed epoch by epoch (``runtime/stream.py``) with
    integer Stats bit-identical to one monolithic run.
    """
    conv_tags: jnp.ndarray    # (B, Sc, Wc) uint32
    conv_valid: jnp.ndarray   # (B, Sc, Wc) bool
    conv_dirty: jnp.ndarray   # (B, Sc, Wc) bool
    conv_lru: jnp.ndarray     # (B, Sc, Wc) uint32
    ext_tags: jnp.ndarray     # (B, Se, We) uint32
    ext_valid: jnp.ndarray    # (B, Se, We) bool
    ext_dirty: jnp.ndarray    # (B, Se, We) bool
    ext_lru: jnp.ndarray      # (B, Se, We) uint32
    ext_size: jnp.ndarray     # (B, Se, We) int32 physical bytes per block
    ext_used: jnp.ndarray     # (B, Se) int32 bytes in use
    bf1: jnp.ndarray          # (B, Se, words) uint32
    bf2: jnp.ndarray          # (B, Se, words) uint32
    n_mru: jnp.ndarray        # (B, Se) int32
    stats: Stats              # accumulated, (B,) leaves
    pos: jnp.ndarray          # (B,) int32 — requests consumed so far


def init_state(cfg: MorpheusConfig, batch: int = 1) -> EngineState:
    """Cold engine state (empty caches, zero stats) for ``batch`` traces."""
    sc, wc = cfg.amap.conv_sets, cfg.conv_ways
    se, we = cfg.amap.ext_sets, cfg.ext_max_ways
    words = ctl.BLOOM_WORDS
    b = batch
    stats = jax.tree.map(
        lambda z: jnp.zeros((b,) + z.shape, z.dtype), ctl._zero_stats())
    return EngineState(
        conv_tags=jnp.zeros((b, sc, wc), jnp.uint32),
        conv_valid=jnp.zeros((b, sc, wc), jnp.bool_),
        conv_dirty=jnp.zeros((b, sc, wc), jnp.bool_),
        conv_lru=jnp.zeros((b, sc, wc), jnp.uint32),
        ext_tags=jnp.zeros((b, se, we), jnp.uint32),
        ext_valid=jnp.zeros((b, se, we), jnp.bool_),
        ext_dirty=jnp.zeros((b, se, we), jnp.bool_),
        ext_lru=jnp.zeros((b, se, we), jnp.uint32),
        ext_size=jnp.zeros((b, se, we), jnp.int32),
        ext_used=jnp.zeros((b, se), jnp.int32),
        bf1=jnp.zeros((b, se, words), jnp.uint32),
        bf2=jnp.zeros((b, se, words), jnp.uint32),
        n_mru=jnp.zeros((b, se), jnp.int32),
        stats=stats,
        pos=jnp.zeros((b,), jnp.int32),
    )


def decode_state(cfg: MorpheusConfig, state: EngineState,
                 trace: int = 0) -> dict:
    """Read-only host-side decode of one trace row's cache contents.

    The introspection layer's view of the carry (``repro.obs.inspect``):
    per-set valid-way counts per tier, dirty-block totals, recovered full
    block addresses (``addr = tag * total_sets + global_set`` — the same
    recovery ``runtime/stream.py::extract_blocks`` uses for handoff),
    extended-tier byte usage + per-resident physical sizes, the BF1 word
    array and the stream position.  Pure numpy over a materialized copy:
    never touches or re-derives device state, so decoding cannot perturb
    a simulation.
    """
    st = jax.tree.map(np.asarray, state)
    total = max(cfg.amap.total_sets, 1)

    conv_valid = st.conv_valid[trace]
    s_idx, w_idx = np.nonzero(conv_valid)
    conv_addr = (st.conv_tags[trace][s_idx, w_idx].astype(np.uint64)
                 * total + s_idx.astype(np.uint64))

    ext_valid = st.ext_valid[trace]
    e_s, e_w = np.nonzero(ext_valid)
    gset = (cfg.amap.conv_sets + e_s).astype(np.uint64)
    ext_addr = (st.ext_tags[trace][e_s, e_w].astype(np.uint64)
                * total + gset)

    return {
        "pos": int(st.pos[trace]),
        "conv_set_occ": conv_valid.sum(axis=1).astype(np.int64),
        "conv_dirty_blocks": int(st.conv_dirty[trace][s_idx, w_idx].sum()),
        "conv_addr": conv_addr,
        "ext_set_occ": ext_valid.sum(axis=1).astype(np.int64),
        "ext_dirty_blocks": int(st.ext_dirty[trace][e_s, e_w].sum()),
        "ext_addr": ext_addr,
        "ext_size_valid": st.ext_size[trace][e_s, e_w].astype(np.int64),
        "ext_used": st.ext_used[trace].astype(np.int64),
        "bf1": st.bf1[trace],
    }


# ------------------------------------------------------------------ engine

def _scan_trace(cfg: MorpheusConfig, slot, rows0, cols):
    """All sets of one tier of ONE trace: (S, ...) state rows and (S, L)
    request columns -> (final rows, Stats summed over sets).  Sets travel
    as columns (``controller`` layout); the scan runs over the L slots."""
    rows = jax.tree.map(lambda x: x.T if x.ndim == 2 else x[None, :], rows0)
    acc = jax.tree.map(lambda z: jnp.zeros((1, cols[0].shape[0]), z.dtype),
                       ctl._zero_stats())

    def body(carry, req):
        row, acc = carry
        row, delta = slot(cfg, row, *req)
        return (row, jax.tree.map(jnp.add, acc, delta)), None

    (rows, acc), _ = jax.lax.scan(body, (rows, acc),
                                  tuple(c.T[:, None, :] for c in cols))
    rows = jax.tree.map(lambda x, x0: x.T if x0.ndim == 2 else x[0],
                        rows, rows0)
    return rows, jax.tree.map(jnp.sum, acc)


def _scan_tier(cfg: MorpheusConfig, slot, rows, cols):
    """jnp engine: ``_scan_trace`` vmapped over the B traces."""
    return jax.vmap(partial(_scan_trace, cfg, slot))(rows, cols)


@partial(jax.jit, static_argnums=(0, 2))
def _run_packed(cfg: MorpheusConfig, pt: PackedTraces,
                backend: str = "jnp") -> Stats:
    """Batched engine from cold caches: PackedTraces -> Stats with (B,)
    leaves."""
    state = init_state(cfg, pt.warmup.shape[0])
    return _run_packed_state(cfg, pt, state, backend)[1]


@partial(jax.jit, static_argnums=(0, 3))
def _run_packed_state(cfg: MorpheusConfig, pt: PackedTraces,
                      state: EngineState, backend: str = "jnp"
                      ) -> Tuple[EngineState, Stats]:
    """Stateful batched engine: one epoch of packed requests applied to an
    explicit carry.  Returns (new state, this epoch's Stats delta)."""
    if backend == "pallas":
        from ..kernels import engine_scan
        scan = engine_scan.scan_tier
    else:
        scan = _scan_tier
    b = pt.warmup.shape[0]
    warm = pt.warmup[:, None, None]
    delta = jax.tree.map(
        lambda z: jnp.zeros((b,) + z.shape, z.dtype), ctl._zero_stats())

    def scan_padded(slot, rows, cols):
        # the packed set axis may hold empty sets past the config's (whole
        # scan tiles, ``pack``): their state rows are empty too, and are
        # dropped again after the scan
        n, s = rows[0].shape[1], cols[0].shape[1]
        if s == n:
            return scan(cfg, slot, rows, cols)
        rows = jax.tree.map(lambda x: jnp.pad(
            x, [(0, 0), (0, s - n)] + [(0, 0)] * (x.ndim - 2)), rows)
        rows, d = scan(cfg, slot, rows, cols)
        return jax.tree.map(lambda x: x[:, :n], rows), d

    if pt.conv_tag.shape[1] and pt.conv_tag.shape[2]:
        rows, d = scan_padded(ctl.conv_slot,
                              ctl.ConvRow(state.conv_tags, state.conv_valid,
                                          state.conv_dirty, state.conv_lru),
                              (pt.conv_tag, pt.conv_write, pt.conv_active,
                               pt.conv_active & (pt.conv_pos >= warm)))
        delta = jax.tree.map(jnp.add, delta, d)
        state = state._replace(conv_tags=rows.tags, conv_valid=rows.valid,
                               conv_dirty=rows.dirty, conv_lru=rows.lru)
    if pt.ext_tag.shape[1] and pt.ext_tag.shape[2]:
        rows, d = scan_padded(ctl.ext_slot,
                              ctl.ExtRow(state.ext_tags, state.ext_valid,
                                         state.ext_dirty, state.ext_lru,
                                         state.ext_size, state.ext_used,
                                         state.bf1, state.bf2, state.n_mru),
                              (pt.ext_tag, pt.ext_write, pt.ext_level,
                               pt.ext_active,
                               pt.ext_active & (pt.ext_pos >= warm)))
        delta = jax.tree.map(jnp.add, delta, d)
        state = state._replace(ext_tags=rows.tags, ext_valid=rows.valid,
                               ext_dirty=rows.dirty, ext_lru=rows.lru,
                               ext_size=rows.size, ext_used=rows.used,
                               bf1=rows.bf1, bf2=rows.bf2, n_mru=rows.n_mru)
    n_req = jnp.zeros((b,), jnp.int32)
    if pt.conv_active.shape[1] and pt.conv_active.shape[2]:
        n_req = n_req + pt.conv_active.sum(axis=(1, 2)).astype(jnp.int32)
    if pt.ext_active.shape[1] and pt.ext_active.shape[2]:
        n_req = n_req + pt.ext_active.sum(axis=(1, 2)).astype(jnp.int32)
    state = state._replace(
        stats=jax.tree.map(jnp.add, state.stats, delta),
        pos=state.pos + n_req)
    return state, delta


def advance_packed(cfg: MorpheusConfig, pt: PackedTraces, state: EngineState,
                   backend: str | None = None
                   ) -> Tuple[EngineState, Stats]:
    """Apply one packed epoch to an ``EngineState``.

    The packed slice must continue exactly where ``state`` left off (pack
    with ``pos0 = state.pos``): requests are replayed in in-set order, so
    integer Stats accumulated over any epoch partition are bit-identical
    to a single monolithic ``simulate_batch`` of the concatenated trace.
    """
    obs.count("engine_dispatches", 1, path="epoch")
    backend = resolve_backend(backend)
    _count_set_tiles(cfg, pt, backend)
    return _run_packed_state(cfg, pt, state, backend)


def _count_set_tiles(cfg: MorpheusConfig, pt: PackedTraces,
                     backend: str) -> None:
    """Counter ``scan_set_tiles{tier}``: the set tiles each Pallas scan of
    a dispatch runs over (one per tier a tile holds)."""
    if backend != "pallas" or not obs.metrics_on():
        return
    for tier, (_, tiles), tag in zip(("conv", "ext"), set_tiling(cfg),
                                     (pt.conv_tag, pt.ext_tag)):
        if tag.shape[1] and tag.shape[2]:
            obs.count("scan_set_tiles", tiles, tier=tier)


def simulate_batch(cfg: MorpheusConfig,
                   traces: Sequence[Tuple[np.ndarray, np.ndarray,
                                          np.ndarray, int]],
                   backend: str | None = None) -> Stats:
    """Simulate a batch of traces under ONE config in one compiled dispatch.

    Returns a Stats whose leaves have a leading (B,) batch dimension, in
    trace order.  All traces share the compiled executable; distinct
    configs (different set counts / flags) compile separately.  ``backend``
    picks the inner-scan implementation (None -> ``default_backend()``).

    Counter ``device_put_bytes{tier="conv"|"ext"}``: the bytes of each
    tier's packed batch arrays the dispatch hands the device.
    """
    obs.count("engine_dispatches", 1, path="batch")
    backend = resolve_backend(backend)
    pt = pack(cfg, traces)
    _count_set_tiles(cfg, pt, backend)
    if obs.metrics_on():
        for tier in ("conv", "ext"):
            obs.count("device_put_bytes",
                      sum(a.nbytes for f, a in zip(pt._fields, pt)
                          if f.startswith(tier + "_")), tier=tier)
    # host side of the dispatch: argument transfer and the launch
    with obs.span("engine.dispatch"):
        return _run_packed(cfg, pt, backend)


def simulate_parallel(cfg: MorpheusConfig, addrs, writes, levels,
                      warmup: int = 0, backend: str | None = None) -> Stats:
    """Drop-in set-parallel replacement for ``controller.simulate``.

    Stats equivalence vs. the serial scan: integer counters exact, float
    sums equal up to accumulation order (tested in tests/test_engine.py).
    """
    out = simulate_batch(cfg, [(addrs, writes, levels, warmup)], backend)
    return jax.tree.map(lambda x: x[0], out)
