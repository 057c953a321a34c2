"""Serving engine: batched decode with a Morpheus two-tier prefix-page
cache.

The engine demonstrates the paper's mechanism end-to-end on the serving
path: prompt KV is chunked into pages keyed by (prefix-hash, layer, page);
requests sharing prefixes *hit* cached pages and skip prefill recompute for
those tokens.  The two-tier pool (``paged_kv.MorpheusPagePool``) decides
where pages live; cache-mode chips extend capacity; the Bloom predictor
keeps extended-tier misses off the interconnect.

Timing is accounted with the TPU tier constants (we run on CPU), so the
benchmark harness can report the paper's metrics (hit rates, predicted
misses, modeled latency) for Morpheus on/off.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import ArchConfig
from ..models.transformer import LM
from . import sampler as S
from .paged_kv import GatherPlan, MorpheusPagePool, PoolConfig, page_key

PAGE_TOKENS = 16


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    tenant: str = ""     # owning tenant label (residency audit only)


def _prefix_hash(tokens: List[int]) -> int:
    h = hashlib.blake2b(np.asarray(tokens, np.int32).tobytes(),
                        digest_size=8).digest()
    return int.from_bytes(h, "little")


@dataclass
class EngineReport:
    steps: int
    generated: int
    page_hit_rate: float
    pages_reused: int
    pages_fetched: int
    modeled_time_ns: float
    pred_miss: int
    false_pos: int
    pages_mismatched: int = 0    # hit pages whose payload read back wrong


class Engine:
    """Greedy continuous-batching-lite engine with Morpheus page cache."""

    def __init__(self, model: LM, params, *, max_len: int = 256,
                 pool: Optional[MorpheusPagePool] = None,
                 morpheus: bool = True):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.morpheus = morpheus
        self.pool = pool or MorpheusPagePool(PoolConfig(
            num_cache_chips=4 if morpheus else 0,
            conv_sets=64, ext_sets_per_chip=32, ways=4))
        self._decode = jax.jit(model.decode_step)
        self.pages_reused = 0
        self.pages_fetched = 0
        self.pages_mismatched = 0

    # ------------------------------------------------------------- serving
    def run(self, requests: List[Request]) -> EngineReport:
        """Serve a batch of requests to completion (equal lengths batch)."""
        b = len(requests)
        plen = len(requests[0].prompt)
        assert all(len(r.prompt) == plen for r in requests), \
            "demo engine batches equal-length prompts"

        # ---- page-cache consultation for prompt KV (prefix caching)
        n_pages = plen // PAGE_TOKENS
        ins = obs.inspector()
        for r in requests:
            for pg in range(n_pages):
                prefix = r.prompt[: (pg + 1) * PAGE_TOKENS]
                key = page_key(_prefix_hash(prefix), 0, pg)
                if ins is not None and r.tenant:
                    # page keys carry no tenant bits; note ownership at
                    # consult time so the pool's residency decode can
                    # attribute resident pages back to tenants
                    ins.note_owner(key, r.tenant)
                plan = self.pool.lookup_batch(np.asarray([key], np.uint32))
                # the page payload is a digest of its prefix: 128 bytes =
                # two 64-byte salted blake2b digests (blake2b caps
                # digest_size at 64)
                raw = bytes(prefix.__repr__(), "utf8")
                digest = (hashlib.blake2b(raw, digest_size=64,
                                          salt=b"pg0").digest() +
                          hashlib.blake2b(raw, digest_size=64,
                                          salt=b"pg1").digest())
                payload = np.frombuffer(digest, dtype=np.uint32)
                if plan.tier[0] == 2:
                    # backing fetch = recompute; install the payload
                    self.pages_fetched += 1
                    self.pool.write_page(key, jnp.asarray(payload))
                else:
                    # a hit reads its page through the data-array path
                    # (Indirect-MOV gather, BDI decompress-on-read)
                    self.pages_reused += 1
                    got = np.asarray(self.pool.read_pages(plan))[0]
                    self.pages_mismatched += int(
                        not np.array_equal(got, payload))

        # ---- real prefill + decode (the compiled model path)
        tokens = jnp.asarray([r.prompt for r in requests], jnp.int32)
        caches = self.model.init_caches(b, self.max_len)
        batch = {"tokens": tokens}
        if self.model.cfg.is_encdec:
            batch["frame_embeds"] = jnp.zeros(
                (b, 8, self.model.cfg.d_model), jnp.float32)
            caches["enc_out"] = self.model._encode(self.params, batch)
        logits, caches = jax.jit(self.model.prefill)(self.params, batch,
                                                     caches)
        steps = 0
        cur = S.greedy(logits)
        max_new = max(r.max_new_tokens for r in requests)
        for t in range(max_new):
            for i, r in enumerate(requests):
                if not r.done:
                    r.out_tokens.append(int(cur[i]))
                    r.done = len(r.out_tokens) >= r.max_new_tokens
            if all(r.done for r in requests):
                break
            logits, caches = self._decode(self.params, cur, caches,
                                          jnp.int32(plen + t))
            cur = S.greedy(logits)
            steps += 1

        st = self.pool.stats
        return EngineReport(
            steps=steps,
            generated=sum(len(r.out_tokens) for r in requests),
            page_hit_rate=self.pool.hit_rate(),
            pages_reused=self.pages_reused,
            pages_fetched=self.pages_fetched,
            modeled_time_ns=st.time_ns,
            pred_miss=st.ext_pred_miss,
            false_pos=st.ext_false_pos,
            pages_mismatched=self.pages_mismatched,
        )
