"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU; asserts output shapes and absence of NaNs.  Also checks
decode-vs-forward consistency for a few representative families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model

ARCHS = sorted(configs.ALL_ARCHS)


def _batch(cfg, rng, batch=2, seq=16):
    r1, r2, r3, r4 = jax.random.split(rng, 4)
    b = {
        "tokens": jax.random.randint(r1, (batch, seq), 0, cfg.vocab_size),
        "targets": jax.random.randint(r2, (batch, seq), 0, cfg.vocab_size),
    }
    if cfg.is_encdec:
        b["frame_embeds"] = jax.random.normal(r3, (batch, 8, cfg.d_model))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                               (3, batch, seq))
        b["positions"] = pos
    if cfg.family == "vlm":
        b["patch_embeds"] = jax.random.normal(r4, (batch, 4, cfg.d_model)) * 0.02
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    batch = _batch(cfg, jax.random.PRNGKey(1))

    logits = jax.jit(lambda p, b: model.forward(p, b))(params, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab())
    assert not bool(jnp.any(jnp.isnan(logits))), f"{arch}: NaN in logits"

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss(p, b)))(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    gnorm = jax.tree.reduce(
        lambda a, x: a + jnp.sum(jnp.square(x.astype(jnp.float32))),
        grads, jnp.float32(0.0))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, f"{arch}: bad grads"

    # one SGD step reduces nothing catastrophic (params stay finite)
    new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                              params, grads)
    loss2 = jax.jit(lambda p, b: model.loss(p, b))(new_params, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", ["qwen3-4b", "gemma3-27b", "mamba2-780m",
                                  "deepseek-v2-lite-16b",
                                  "jamba-1.5-large-398b",
                                  "seamless-m4t-medium"])
def test_decode_matches_forward(arch):
    """Greedy decode logits must match teacher-forced forward logits."""
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1), batch=1, seq=8)
    tokens = batch["tokens"]

    full = model.forward(params, batch)  # (1, 8, V)

    caches = model.init_caches(batch_size=1, max_len=16)
    if cfg.is_encdec:
        caches["enc_out"] = model._encode(params, batch)
    step = jax.jit(model.decode_step)
    for t in range(8):
        logits, caches = step(params, tokens[:, t], caches,
                              jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, t]),
            rtol=2e-2, atol=2e-2,
        )


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_estimate_close(arch):
    """ArchConfig.param_count must track actual init sizes on reduced cfgs
    (within 25%)."""
    cfg = configs.get(arch).reduced()
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    est, _ = cfg.param_count()
    assert abs(actual - est) / actual < 0.25, (arch, actual, est)
