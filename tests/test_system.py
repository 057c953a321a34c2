"""End-to-end system tests: the full stack working together.

These exercise the public API the examples use — train loop with
checkpoint/restart + fault supervisor, the serving engine with the
Morpheus tier, and the mode-split policy — on reduced configs.
"""
import jax
import numpy as np

from repro import configs
from repro.core import cache_sim as cs
from repro.core.policy import best_split
from repro.models import build_model
from repro.serving import Engine, Request
from repro.train.loop import train


def test_train_loop_decreases_loss_and_checkpoints(tmp_path):
    cfg = configs.get("h2o-danube-1.8b").reduced()
    state, losses, rep = train(cfg, steps=24, batch=4, seq=64,
                               ckpt_dir=str(tmp_path), ckpt_every=8)
    assert rep.steps_run == 24
    assert losses[-1] < losses[0]
    # restart resumes from the persisted step and continues
    state2, losses2, rep2 = train(cfg, steps=30, batch=4, seq=64,
                                  ckpt_dir=str(tmp_path), ckpt_every=100)
    assert rep2.resumed_from == 24
    assert rep2.steps_run == 6


def test_training_step_is_deterministic():
    cfg = configs.get("qwen3-4b").reduced()
    out = []
    for _ in range(2):
        _, losses, _ = train(cfg, steps=4, batch=2, seq=32, seed=7)
        out.append(losses)
    np.testing.assert_allclose(out[0], out[1], rtol=1e-6)


def test_serving_engine_morpheus_transparent_second_arch():
    """The extended tier must never change generated tokens (gemma2:
    local+global alternating layers + softcap)."""
    cfg = configs.get("gemma2-9b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [(3 * j + 5) % 97 + 1 for j in range(24)]
    outs = []
    for morpheus in (True, False):
        reqs = [Request(rid=0, prompt=prompt, max_new_tokens=5)]
        Engine(model, params, max_len=48, morpheus=morpheus).run(reqs)
        outs.append(reqs[0].out_tokens)
    assert outs[0] == outs[1]
    assert len(outs[0]) == 5


def test_policy_mode_split_sane():
    """The Table-3 analogue: memory-bound apps give cores to the cache
    tier; the chosen split must beat the all-compute baseline."""
    split = best_split("kmeans", "Morpheus-ALL", length=16_000)
    gpu = cs.SYSTEMS["Morpheus-ALL"].gpu
    assert 0 < split.n_cache <= int(gpu.total_cores * cs.MAX_CACHE_FRAC)
    assert split.n_compute + split.n_cache <= gpu.total_cores
    bl = cs.run("kmeans", "BL", n_compute=gpu.total_cores, length=16_000)
    assert split.exec_time_s < bl.exec_time_s
