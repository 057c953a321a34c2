"""Plain reference of the simulated system: one request at a time.

What a sweep point computes, written from the paper's description and the
configuration file alone (it imports nothing of the program):

* static address separation: global set ``addr % total_sets``, tag
  ``addr // total_sets``; sets below ``conv_sets`` are the conventional
  LLC, the rest the extended LLC of the cache-mode cores;
* conventional LLC: set-associative, LRU (Algorithm 1 counters: a touch
  sets 4095, every other way of the set counts down to 0, the victim is
  the lowest counter, ties to the lowest way), insert into the lowest free
  way, dirty write-back on eviction;
* extended LLC: the same LRU over a byte budget of ``ext_ways`` x 128 B;
  with BDI compression a block takes 32, 64 or 128 B by its level, and an
  insert evicts LRU blocks (at most four) until it fits;
* double Bloom predictor (Fig. 6): a request is forwarded iff BF1 may
  hold its tag; every extended access inserts into both filters, counts
  ``n`` up when BF2 did not hold it, and swaps at ``n >= ext_ways``;
* Stats: integer counters per request from ``warmup`` on; the float sums
  follow from the counters and the configuration's costs, in float64;
* the analytical execution-time and power model on top (``finalize``).

LRU counters are kept as the set-access index of each block's last touch:
a block touched at index ``j`` reads ``max(0, 4095 - (k - 1 - j))`` at
access ``k``, which is what counting down gives.
"""
from __future__ import annotations

import heapq
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from . import tracegen

INT_FIELDS = ("conv_hits", "conv_misses", "ext_hits", "ext_false_pos",
              "ext_pred_miss", "ext_true_miss", "dram_accesses",
              "writebacks", "bloom_swaps")
FLOAT_FIELDS = ("latency_ns", "energy_nJ", "noc_bytes", "conv_bytes",
                "dram_bytes")


@dataclass(frozen=True)
class Geometry:
    """The simulated caches of one point."""
    conv_sets: int
    ext_sets: int
    conv_ways: int
    ext_ways: int
    compression: bool
    bloom: bool
    block: int
    lru_max: int
    bloom_bits: int
    multipliers: Tuple[int, ...]

    @property
    def total_sets(self) -> int:
        return self.conv_sets + self.ext_sets

    @property
    def ext_budget(self) -> int:
        return self.ext_ways * self.block

    @property
    def ext_max_ways(self) -> int:
        return self.ext_ways * (self.block // 32) if self.compression \
            else self.ext_ways


def grid_split(config: dict, app: str, n_compute: int) -> int | None:
    """Cache-mode cores of a Table-3 grid entry (None: not a point).
    Morpheus gives the rest of the cores to cache mode, up to
    ``max_cache_frac`` of them; other systems power-gate the rest."""
    gpu, flags = config["gpu"], config["flags"]
    if not (flags["morpheus"] and tracegen.load_apps()[app]["memory_bound"]):
        return 0
    n_cache = min(gpu["total_cores"] - n_compute,
                  int(gpu["total_cores"] * gpu["max_cache_frac"]))
    return n_cache if n_cache > 0 else None


def geometry(config: dict, n_cache: int) -> Geometry:
    gpu, flags = config["gpu"], config["flags"]
    scale, block = config["sim_scale"], gpu["block_bytes"]
    conv_bytes = int(gpu["conv_llc_bytes"] * flags["conv_scale"]) // scale
    conv_sets = max(conv_bytes // (gpu["conv_ways"] * block),
                    gpu["min_conv_sets"])
    per_core = max(gpu["ext_bytes_per_core"] // (gpu["ext_ways"] * block)
                   // scale, gpu["min_ext_sets_per_core"])
    ext_sets = per_core * n_cache if flags["morpheus"] else 0
    return Geometry(conv_sets=conv_sets, ext_sets=ext_sets,
                    conv_ways=gpu["conv_ways"], ext_ways=gpu["ext_ways"],
                    compression=flags["compression"],
                    bloom=flags.get("predictor") == "bloom", block=block,
                    lru_max=gpu["lru_max"], bloom_bits=gpu["bloom_bits"],
                    multipliers=tuple(gpu["bloom_multipliers"]))


class _Set:
    """One cache set: blocks in LRU order, free ways, access count."""
    __slots__ = ("blocks", "free", "k", "used", "bf1", "bf2", "n")

    def __init__(self, ways: int):
        self.blocks: "OrderedDict[int, list]" = OrderedDict()
        self.free: List[int] = list(range(ways))
        self.k = 0          # accesses so far
        self.used = 0       # bytes held (extended tier)
        self.bf1 = self.bf2 = self.n = 0


def _victim(s: _Set, lru_max: int) -> int:
    """Tag of the block with the lowest LRU counter (lowest way on ties).
    Counters are distinct until they saturate at 0, so the oldest block
    is the victim unless several have counted down to 0."""
    first = next(iter(s.blocks))
    if s.k - 1 - s.blocks[first][2] < lru_max:
        return first
    tied = [t for t, b in s.blocks.items() if s.k - 1 - b[2] >= lru_max]
    return min(tied, key=lambda t: s.blocks[t][0])


def simulate(geo: Geometry, addrs: np.ndarray, writes: np.ndarray,
             levels: np.ndarray, warmup: int) -> Dict[str, int]:
    """Replay one trace; integer Stats plus the extended tier's
    write-backs (``ext_writebacks``), which the float sums need."""
    conv = [_Set(geo.conv_ways) for _ in range(geo.conv_sets)]
    ext = [_Set(geo.ext_max_ways) for _ in range(geo.ext_sets)]
    c = dict.fromkeys(INT_FIELDS + ("ext_writebacks",), 0)
    total, nconv = geo.total_sets, geo.conv_sets
    lru_max, budget, bits_n = geo.lru_max, geo.ext_budget, geo.bloom_bits
    mults = geo.multipliers
    phys_of = ({0: 32, 1: 64} if geo.compression else {})
    for i, (a, w, lvl) in enumerate(zip(addrs.tolist(), writes.tolist(),
                                        levels.tolist())):
        g, tag = a % total, a // total
        counted = i >= warmup
        if g < nconv:
            s = conv[g]
            b = s.blocks.get(tag)
            if b is not None:
                b[1] = b[1] or w
                b[2] = s.k
                s.blocks.move_to_end(tag)
                c["conv_hits"] += counted
            else:
                wb = False
                if s.free:
                    way = heapq.heappop(s.free)
                else:
                    vt = _victim(s, lru_max)
                    way, wb, _, _ = s.blocks.pop(vt)
                s.blocks[tag] = [way, w, s.k, 0]
                if counted:
                    c["conv_misses"] += 1
                    c["dram_accesses"] += 1
                    c["writebacks"] += wb
            s.k += 1
            continue
        s = ext[g - nconv]
        bits = 0
        for m in mults:
            hm = (tag * m) & 0xFFFFFFFF
            bits |= 1 << ((hm ^ (hm >> 15)) % bits_n)
        pred = (s.bf1 & bits) == bits if geo.bloom else True
        b = s.blocks.get(tag)
        hit = b is not None
        wbs = 0
        if hit:
            b[1] = b[1] or w
            b[2] = s.k
            s.blocks.move_to_end(tag)
        else:
            phys = phys_of.get(lvl, geo.block)
            for _ in range(geo.block // 32):
                if s.used + phys > budget and s.blocks:
                    way, dirty, _, size = s.blocks.pop(_victim(s, lru_max))
                    wbs += dirty
                    s.used -= size
                    heapq.heappush(s.free, way)
            if not s.free:
                raise RuntimeError("extended set full after evictions")
            s.blocks[tag] = [heapq.heappop(s.free), w, s.k, phys]
            s.used += phys
        swap = False
        if geo.bloom:
            was_in_bf2 = (s.bf2 & bits) == bits
            s.bf1 |= bits
            s.bf2 |= bits
            s.n += not was_in_bf2
            if s.n >= geo.ext_ways:
                s.bf1, s.bf2, s.n, swap = s.bf2, 0, 0, True
        s.k += 1
        if counted:
            c["ext_hits"] += hit
            c["ext_false_pos"] += (not hit) and pred
            c["ext_pred_miss"] += not pred
            c["ext_true_miss"] += not hit
            c["dram_accesses"] += not hit
            c["writebacks"] += wbs
            c["ext_writebacks"] += wbs
            c["bloom_swaps"] += swap
    return c


def latencies(config: dict) -> Tuple[float, float, float, float, float]:
    """(conv hit, conv miss, ext hit, ext miss, predicted miss) in ns."""
    gpu, flags = config["gpu"], config["flags"]
    ext_hit, ext_miss = gpu["ext_llc"]["hit_ns"], gpu["ext_llc"]["miss_ns"]
    if flags["indirect_mov"]:
        ext_hit -= gpu["indirect_mov_latency_cut_ns"]
        ext_miss -= gpu["indirect_mov_latency_cut_ns"]
    if flags["compression"]:
        ext_hit += gpu["compression_hit_latency_ns"]
    return (gpu["conv_llc"]["hit_ns"], gpu["conv_llc"]["miss_ns"], ext_hit,
            ext_miss, gpu["predicted_miss_ns"])


def float_stats(config: dict, c: Dict[str, int]) -> Dict[str, float]:
    """The float Stats, exact from the counters (float64)."""
    gpu = config["gpu"]
    block = gpu["block_bytes"]
    lch, lcm, leh, lem, lpm = latencies(config)
    e_conv = block * gpu["conv_llc"]["pj_per_byte"] * 1e-3
    e_ext = block * gpu["ext_llc"]["pj_per_byte"] * 1e-3
    e_dram = block * gpu["dram"]["pj_per_byte"] * 1e-3
    conv_acc = c["conv_hits"] + c["conv_misses"]
    return {
        "latency_ns": (c["conv_hits"] * lch + c["conv_misses"] * lcm
                       + c["ext_hits"] * leh + c["ext_false_pos"] * lem
                       + c["ext_pred_miss"] * lpm),
        "energy_nJ": (conv_acc * e_conv
                      + (c["ext_hits"] + c["ext_false_pos"]) * e_ext
                      + c["ext_pred_miss"] * e_ext
                      * gpu["predictor_only_energy_frac"]
                      + (c["dram_accesses"] + c["writebacks"]) * e_dram),
        "noc_bytes": float((c["ext_hits"] + c["ext_false_pos"]
                            + c["ext_true_miss"] + c["ext_writebacks"])
                           * block),
        "conv_bytes": float(conv_acc * block),
        "dram_bytes": float((c["dram_accesses"] + c["writebacks"]) * block),
    }


def finalize(config: dict, app: str, n_compute: int, n_cache: int,
             n_acc: int, f: Dict[str, float]) -> Dict[str, float]:
    """Execution time and IPC of a point: the slowest of compute, DRAM,
    conventional LLC, interconnect, extended-tier service and latency."""
    gpu, flags = config["gpu"], config["flags"]
    spec = tracegen.load_apps()[app]
    insts = spec["inst_per_access"] * n_acc
    freq = gpu["freq_ghz"] * 1e9
    boost = flags["mem_boost"]
    t_compute = insts / (n_compute * gpu["ipc_per_core"] * freq)
    row_locality = max(0.2, min(1.0, spec["contention_knee"]
                                / max(n_compute, 1)))
    t_dram = f["dram_bytes"] / (gpu["bw_dram"] * boost * row_locality)
    t_conv = f["conv_bytes"] / (gpu["bw_conv"] * boost)
    t_noc = f["noc_bytes"] / (gpu["bw_noc"] * boost)
    ext_bw = gpu["bw_ext_core"] * (gpu["indirect_mov_ext_bw_gain"]
                                   if flags["indirect_mov"] else 1.0)
    t_ext = (f["noc_bytes"] / (max(n_cache, 1) * ext_bw)
             if flags["morpheus"] and n_cache else 0.0)
    t_lat = f["latency_ns"] * 1e-9 / (boost * n_compute
                                      * gpu["mlp_per_core"])
    t_exec = max(t_compute, t_dram, t_conv, t_noc, t_ext, t_lat)
    ipc = insts / (t_exec * freq) if t_exec > 0 else 0.0
    return {"exec_time_s": t_exec, "ipc": ipc}


def run_point(config: dict, app: str, n_compute: int, n_cache: int,
              length: int, seed: int, round_floats=None) -> dict:
    """Everything a sweep point reports: integer Stats, float Stats,
    execution time and IPC.  ``round_floats`` (the control's) rounds the
    float Stats before the execution-time model reads them."""
    scale = config["sim_scale"]
    addrs, writes, levels = tracegen.generate(
        app, n_cores=n_compute, length=length, seed=seed,
        ws_scale=1.0 / scale)
    spec = tracegen.load_apps()[app]
    ws_blocks = spec["working_set_bytes"] // scale \
        // config["gpu"]["block_bytes"]
    warmup = int(min(len(addrs) // 2, ws_blocks))
    c = simulate(geometry(config, n_cache), addrs, writes, levels, warmup)
    f = float_stats(config, c)
    if round_floats is not None:
        f = {k: round_floats(v) for k, v in f.items()}
    out = {k: c[k] for k in INT_FIELDS}
    out.update(f)
    out.update(finalize(config, app, n_compute, n_cache,
                        len(addrs) - warmup, f))
    return out
