"""Per-architecture smoke tests: reduced same-family config, one forward +
one train-gradient step on CPU; asserts output shapes and finiteness.
The FULL configs are exercised only via the dry-run (ShapeDtypeStructs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.models import build_model

ARCHS = list_archs()
B, T = 2, 64


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {}
    if cfg.frontend == "audio_frames":
        batch["frames"] = jax.random.normal(ks[0], (B, T, cfg.d_model)) * 0.1
        batch["labels"] = jax.random.randint(ks[1], (B, T), 0, cfg.vocab_size)
    elif cfg.frontend == "vision_patches":
        n_patch = 16
        batch["patches"] = (
            jax.random.normal(ks[0], (B, n_patch, cfg.d_model)) * 0.1
        )
        batch["tokens"] = jax.random.randint(
            ks[1], (B, T - n_patch), 0, cfg.vocab_size
        )
        batch["labels"] = jax.random.randint(
            ks[2], (B, T - n_patch), 0, cfg.vocab_size
        )
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, T), 0, cfg.vocab_size)
        batch["labels"] = jax.random.randint(ks[1], (B, T), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_grad_step(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))

    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, batch
    )
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    # loss should be near log(vocab) at random init
    assert float(metrics["ce"]) < np.log(cfg.vocab_size) * 3
    gnorm = jnp.sqrt(
        sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    )
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, f"{arch}: bad grads"

    logits, _ = model.forward(params, batch)
    t_out = batch["labels"].shape[1]
    if cfg.frontend == "vision_patches":
        assert logits.shape == (B, T, cfg.vocab_size)
    else:
        assert logits.shape == (B, t_out, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize(
    "arch", ["qwen3-14b", "deepseek-v2-lite-16b", "zamba2-1.2b", "zamba2-7b",
             "xlstm-350m", "stablelm-3b", "gemma3-27b"]
)
def test_prefill_decode_consistency(arch):
    """Prefill+decode logits must match full-sequence forward (teacher
    forcing) — the serving-correctness contract."""
    cfg = get_arch(arch).reduced()
    # high capacity factor: capacity drops must not differ between the
    # prefill and full-forward runs for an exact comparison
    model = build_model(cfg, moe_capacity_factor=16.0)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)

    full_logits, _ = model.forward(params, {"tokens": tokens})

    cache = model.init_cache(batch=1, s_max=32, dtype=jnp.float32)
    pre_logits, cache, length = model.prefill(
        params, {"tokens": tokens[:, :8]}, cache
    )
    np.testing.assert_allclose(
        pre_logits[:, 0], full_logits[:, 7], rtol=2e-2, atol=2e-2
    )
    cache_len = jnp.asarray(8, jnp.int32)
    for t in range(8, 12):
        logits, cache, cache_len = model.decode_step(
            params, tokens[:, t : t + 1], cache, cache_len
        )
        np.testing.assert_allclose(
            logits[:, 0], full_logits[:, t], rtol=2e-2, atol=2e-2,
            err_msg=f"{arch} step {t}",
        )
