"""Pallas kernel: fused cache-engine transition scan over all sets.

This is the engine's hot path — the per-set state machine of
``core/engine._run_packed_state`` — as a purpose-built kernel, in the spirit
of the Morpheus helper kernel itself (and of assist-warp designs like
CALDERA, arXiv:1602.01348): move the bottleneck state machine into a
kernel that lives next to the memory it manages.

Layout (sets on lanes, the form Mosaic lowers):

  * grid = (B, S / T, L / Lc): one program instance per (trace, tile of T
    sets, chunk of Lc request slots).  Sets are independent, so the set
    axis is "parallel"; the slot axis is sequential ("arbitrary"): chunks
    of one tile run in order and its state stays resident in VMEM across
    them.  ``set_tiling`` picks T: all S sets where their blocks fit
    ``BLOCK_BUDGET`` (one tile: every shape of the 1/8-scale systems),
    else the fewest equal tiles of whole 128-lane groups that fit;
    ``engine.pack`` pads the set axis to whole tiles with empty sets.
  * in_specs: the packed request columns, transposed to (B, L, S) and
    tiled (1, Lc, T) — one sublane row per slot, one lane per set.  Slot
    ``t`` of every set of the tile is one dynamic-row load ``col[0, t]``.
  * state: every row leaf as a (1, ways, T) / (1, words, T) / (1, 1, T)
    block (one column per set; bools as int32).  Instance 0 of a tile
    copies the input state into the output block, which then carries the
    state across the slot chunks.
  * body: ``lax.fori_loop`` over the chunk's slots, applying the SAME
    column transition kernels the serial oracle and the jnp engine run
    (``controller.conv_slot`` / ``ext_slot``) to all S sets at once, and
    accumulating the per-set Stats deltas in the loop carry (int32
    counters exact, float32 sums in in-set order).
  * out_specs: per-set Stats rows (B, n_int, S) int32 and (B, n_float, S)
    float32, reduced over sets by the caller, plus the final state.

Because the transition functions are literally shared with the serial
``lax.scan`` oracle and the jnp engine, the integer Stats are bit-identical
across all three paths (property-tested in tests/test_engine.py).  The
kernels only use 2-D values, int32/bool reductions over the ways axis and
dynamic sublane-row loads, so they compile for TPU v5e
(tests/test_tpu_compile.py).  On the CPU backend they run in interpret mode
(``kernels.ops.interpret_mode``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import controller as ctl
from ..core.controller import Stats
from . import ops

# Stats layout inside the kernel: one int32 block + one float32 block,
# field order inherited from the Stats NamedTuple.
INT_FIELDS: Tuple[str, ...] = tuple(
    f for f in Stats._fields if f in ctl._INT_FIELDS)
FLOAT_FIELDS: Tuple[str, ...] = tuple(
    f for f in Stats._fields if f not in ctl._INT_FIELDS)
_NI, _NF = len(INT_FIELDS), len(FLOAT_FIELDS)

# request slots per grid step (the packed L is a power of two)
SLOT_CHUNK = 128
# What one kernel instance's blocks may take: half of Mosaic's default
# scoped VMEM on TPU v5e (16 MiB), whose other half holds the body's
# temporaries.  The extended tier's slot keeps (ways, T) values there about
# as large as its blocks: at 768 lanes a v5e compile asks for 16.5 MiB
# against 11.6 MiB of blocks, and fails; at 640 it fits.
BLOCK_BUDGET = 8 * 2**20
LANES = 128


def _vmem_bytes(tile: int, state_rows: int, n_cols: int) -> int:
    """VMEM of one instance's blocks for ``tile`` sets, every block
    double-buffered: the request columns, the state in and out, and the
    Stats rows (sublane-padded).  Lanes round up to whole 128-lane
    groups."""
    stats = sum(-(-n // 8) * 8 for n in (_NI, _NF))
    rows = 2 * (n_cols * SLOT_CHUNK + 2 * state_rows + stats)
    return 4 * rows * (-(-tile // LANES) * LANES)


def set_tiling(n_sets: int, state_rows: int, n_cols: int
               ) -> Tuple[int, int]:
    """(sets per tile, tiles) of a tier of ``n_sets`` sets whose state is
    ``state_rows`` int32 rows per set and whose slot takes ``n_cols``
    request columns: one tile of all sets where their blocks fit
    ``BLOCK_BUDGET``, else the fewest equal tiles of whole 128-lane groups
    that do."""
    if _vmem_bytes(n_sets, state_rows, n_cols) <= BLOCK_BUDGET:
        return n_sets, 1
    groups = -(-n_sets // LANES)
    for tiles in range(2, groups + 1):
        tile = -(-groups // tiles) * LANES
        if _vmem_bytes(tile, state_rows, n_cols) <= BLOCK_BUDGET:
            return tile, -(-n_sets // tile)
    raise ValueError(f"the blocks of one 128-lane group of a "
                     f"{state_rows}-row state exceed the VMEM budget")


def supported() -> Tuple[bool, str]:
    """Whether this kernel can run on the current host, and how."""
    plat = jax.default_backend()
    if plat == "tpu":
        return True, "compiled Mosaic kernel"
    if plat == "cpu":
        return True, "interpret mode (CPU host)"
    return False, f"no Pallas TPU lowering for '{plat}' hosts"


def _scan_kernel(cfg, slot, row_type, col_bool, row_bool, *refs):
    """One (trace, slot chunk): replay the chunk's slots on every set."""
    n_col, n_row = len(col_bool), len(row_bool)
    cols = refs[:n_col]
    rows_in = refs[n_col:n_col + n_row]
    ints_ref, flts_ref = refs[n_col + n_row:n_col + n_row + 2]
    rows = refs[n_col + n_row + 2:]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        for src, dst in zip(rows_in, rows):
            dst[...] = src[...]
        ints_ref[...] = jnp.zeros_like(ints_ref)
        flts_ref[...] = jnp.zeros_like(flts_ref)

    def body(t, acc):
        req = [c[0, pl.ds(t, 1), :] for c in cols]
        req = [r != 0 if b else r for r, b in zip(req, col_bool)]
        row = row_type(*[r[0] != 0 if b else r[0]
                         for r, b in zip(rows, row_bool)])
        row, delta = slot(cfg, row, *req)
        for r, v, b in zip(rows, row, row_bool):
            r[0] = v.astype(jnp.int32) if b else v
        return jax.tree.map(jnp.add, acc, delta)

    acc = Stats(**{f: ints_ref[0, i:i + 1, :]
                   for i, f in enumerate(INT_FIELDS)},
                **{f: flts_ref[0, i:i + 1, :]
                   for i, f in enumerate(FLOAT_FIELDS)})
    acc = jax.lax.fori_loop(0, cols[0].shape[1], body, acc)
    for i, f in enumerate(INT_FIELDS):
        ints_ref[0, i:i + 1, :] = getattr(acc, f)
    for i, f in enumerate(FLOAT_FIELDS):
        flts_ref[0, i:i + 1, :] = getattr(acc, f)


def _columns(x: jnp.ndarray) -> jnp.ndarray:
    """(B, S, w) state leaf -> (B, w, S); (B, S) per-set scalar -> (B, 1, S);
    bools as int32 (Mosaic keeps no bool memory)."""
    x = jnp.swapaxes(x, 1, 2) if x.ndim == 3 else x[:, None, :]
    return x.astype(jnp.int32) if x.dtype == jnp.bool_ else x


def scan_tier(cfg: ctl.MorpheusConfig, slot, rows, cols, *,
              interpret: bool | None = None):
    """Pallas twin of ``core.engine._scan_tier``: replay B traces' packed
    request columns on one tier.

    ``slot`` is ``controller.conv_slot`` or ``ext_slot``; ``rows`` its row
    NamedTuple with (B, S, ...) state leaves; ``cols`` the slot's request
    columns, each (B, S, L), counted mask last; S is whole tiles of
    ``set_tiling``.  Returns (final rows, Stats with (B,) leaves)."""
    interpret = ops.interpret_mode() if interpret is None else interpret
    b, s, length = cols[0].shape
    lc = SLOT_CHUNK if length % SLOT_CHUNK == 0 else length
    state_rows = sum(x.shape[2] if x.ndim == 3 else 1 for x in rows)
    tile, tiles = set_tiling(s, state_rows, len(cols))
    if tile * tiles != s:
        raise ValueError(f"{s} sets are not whole tiles of {tile}: pack "
                         f"with engine.pack")
    col_bool = tuple(c.dtype == jnp.bool_ for c in cols)
    row_bool = tuple(x.dtype == jnp.bool_ for x in rows)
    cols_k = [jnp.swapaxes(c, 1, 2) for c in cols]
    cols_k = [c.astype(jnp.int32) if c.dtype == jnp.bool_ else c
              for c in cols_k]
    rows_k = [_columns(x) for x in rows]
    out_shape = ([jax.ShapeDtypeStruct((b, _NI, s), jnp.int32),
                  jax.ShapeDtypeStruct((b, _NF, s), jnp.float32)]
                 + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in rows_k])

    def sets(x):
        return pl.BlockSpec((1, x.shape[1], tile), lambda i, k, j: (i, 0, k))

    ints, flts, *new = pl.pallas_call(
        functools.partial(_scan_kernel, cfg, slot, type(rows), col_bool,
                          row_bool),
        grid=(b, tiles, length // lc),
        in_specs=([pl.BlockSpec((1, lc, tile), lambda i, k, j: (i, j, k))]
                  * len(cols_k) + [sets(x) for x in rows_k]),
        out_specs=[sets(o) for o in out_shape],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"engine_scan_{slot.__name__}",
    )(*cols_k, *rows_k)
    new_rows = []
    for x, y in zip(rows, new):
        y = jnp.swapaxes(y, 1, 2) if x.ndim == 3 else y[:, 0, :]
        new_rows.append(y != 0 if x.dtype == jnp.bool_ else y)
    ints, flts = ints.sum(axis=2), flts.sum(axis=2)
    stats = Stats(**{f: ints[:, i] for i, f in enumerate(INT_FIELDS)},
                  **{f: flts[:, i] for i, f in enumerate(FLOAT_FIELDS)})
    return type(rows)(*new_rows), stats
