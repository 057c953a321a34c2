"""Public jit'd wrappers for the Pallas kernels.

One thin function per kernel (tag_lookup, bdi_compress/decompress,
gather_blocks, bloom_query, decode_attention, flash_attention, plus the
fused ``cached_block_read`` composition).  ``interpret_mode`` is the one
place that decides whether a kernel runs compiled or interpreted: compiled
on every backend but the CPU, where the tests run the kernels in interpret
mode.  The kernel modules take ``interpret`` as a required keyword; these
wrappers pass ``interpret_mode()`` unless the caller forces a value.  The
engine's Pallas backend (engine_scan.py) is not wrapped here: it is
selected through ``core.engine``'s ``backend`` switch instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs
from . import bdi as _bdi
from . import bloom_query as _bq
from . import decode_attn as _da
from . import gather_blocks as _gb
from . import tag_lookup as _tl


def interpret_mode() -> bool:
    """True only on the CPU backend: Pallas kernels run interpreted there
    and compiled everywhere else."""
    return jax.default_backend() == "cpu"


def _padded(kernel, block: int, *arrays, interpret):
    """Run a row-tiled pool kernel on a batch of any size: pad the rows to
    a power of two >= 8 (up to ``block``) or a multiple of ``block``, so
    per-round batches reuse a handful of compiled shapes and every block
    is (8, 128)-legal; cut the outputs back to the batch."""
    n = arrays[0].shape[0]
    m = max(8, 1 << (n - 1).bit_length()) if n <= block else \
        -(-n // block) * block
    interpret = interpret_mode() if interpret is None else interpret
    obs.count("pallas_calls", 1, kernel=kernel.__name__,
              interpret=str(interpret))
    return _run_padded(kernel, n, m, interpret, *arrays)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _run_padded(kernel, n: int, m: int, interpret: bool, *arrays):
    """Pad, run and cut back as one dispatch."""
    pad = [jnp.pad(a, [(0, m - n)] + [(0, 0)] * (a.ndim - 1))
           for a in arrays]
    return jax.tree.map(lambda o: o[:n], kernel(*pad, interpret=interpret))


def tag_lookup(tags, valid, lru, req, *, interpret=None):
    """Algorithm-1 tag lookup over all sets: (hit, way, new_lru)."""
    return _padded(_tl.tag_lookup, _tl.SET_BLOCK, tags,
                   jnp.asarray(valid).astype(jnp.int32), lru, req,
                   interpret=interpret)


def bdi_compress(blocks, *, interpret=None):
    return _padded(_bdi.bdi_compress, _bdi.BLOCKS_PER_TILE, blocks,
                   interpret=interpret)


def bdi_decompress(level, base, payload, *, interpret=None):
    return _padded(_bdi.bdi_decompress, _bdi.BLOCKS_PER_TILE, level, base,
                   payload, interpret=interpret)


def gather_blocks(data, way, *, interpret=None):
    """Indirect-MOV data-array access: select the hit way's block."""
    return _padded(_gb.gather_blocks, _gb.SET_BLOCK, data, way,
                   interpret=interpret)


def bloom_query(filters, tags, *, interpret=None):
    """(predicted (Q,) i32, insert_masks (Q, words) u32)."""
    return _padded(_bq.bloom_query, _bq.QUERY_BLOCK, filters, tags,
                   interpret=interpret)


def decode_attention(q, k, v, valid, *, interpret=None, t_block=None):
    it = interpret_mode() if interpret is None else interpret
    kw = {"t_block": t_block} if t_block else {}
    return _da.decode_attention(q, k, v, valid, interpret=it, **kw)


def cached_block_read(data, way, level, base, *, interpret=None):
    """Fused extended-LLC read path: Indirect-MOV gather + BDI
    decompress-on-read (beyond-paper fusion — one VMEM round trip)."""
    payload = gather_blocks(data, way, interpret=interpret)
    return bdi_decompress(level, base, payload, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    scale=None, interpret=None):
    from . import flash_attn as _fa
    it = interpret_mode() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, interpret=it)
