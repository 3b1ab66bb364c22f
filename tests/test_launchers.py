"""End-to-end launcher smoke: train and serve CLIs on reduced configs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_train_launcher_end_to_end(tmp_path):
    from repro.launch.train import main

    result = main([
        "--arch", "stablelm-3b", "--reduced", "--batch", "4", "--seq", "32",
        "--steps", "8", "--ckpt-dir", str(tmp_path), "--ckpt-every", "4",
    ])
    assert len(result["losses"]) == 8
    assert np.isfinite(result["losses"]).all()
    # checkpoints were produced
    assert any(p.name.startswith("step_") for p in tmp_path.iterdir())


def test_train_launcher_resumes(tmp_path):
    from repro.launch.train import main

    main([
        "--arch", "stablelm-3b", "--reduced", "--batch", "4", "--seq", "32",
        "--steps", "6", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
    ])
    out = main([
        "--arch", "stablelm-3b", "--reduced", "--batch", "4", "--seq", "32",
        "--steps", "10", "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
    ])
    assert out["final_step"] == 10


def test_serve_launcher_end_to_end():
    from repro.launch.serve import main

    toks = main([
        "--arch", "qwen3-14b", "--reduced", "--batch", "2",
        "--prompt-len", "8", "--gen", "4",
    ]).decoded.tokens
    assert toks.shape == (2, 4)
    assert bool(jnp.isfinite(toks).all())


def test_grad_accumulation_matches_single_batch():
    """n_microbatches=4 must equal one full-batch step (same grads)."""
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.optim import AdamW, AdamWConfig
    from repro.train.train_loop import make_train_step, train_init
    from repro.data.pipeline import make_batch
    from repro.configs.shapes import InputShape

    cfg = get_arch("stablelm-3b").reduced()
    model = build_model(cfg)
    opt = AdamW(AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=1))
    state = train_init(model, opt, jax.random.PRNGKey(0))
    batch = make_batch(cfg, InputShape("t", 32, 8, "train"), 0)

    g1, g4 = {}, {}

    def cap(store):
        def tf(g):
            store["g"] = g
            return g
        return tf

    s1 = make_train_step(model, opt, compute_dtype=jnp.float32,
                         grad_transform=cap(g1))
    s4 = make_train_step(model, opt, compute_dtype=jnp.float32,
                         n_microbatches=4, grad_transform=cap(g4))
    st1, m1 = s1(state, batch)
    st4, m4 = s4(state, batch)
    np.testing.assert_allclose(
        float(m1["loss"]), float(m4["loss"]), rtol=1e-5
    )
    # The real invariant: the ACCUMULATED GRADS are equal (up to the fp
    # noise of the split-batch reduction order).
    for a, b in zip(jax.tree.leaves(g1["g"]), jax.tree.leaves(g4["g"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # Params after one AdamW step: the bias-corrected first step is
    # ~sign(g)*lr per element, so an infinitesimal grad whose sign flips
    # under reduction-order noise moves the param by up to 2*lr — bound
    # the comparison by that, not by the grad tolerance.
    l1 = jax.tree.leaves(st1.params)
    l4 = jax.tree.leaves(st4.params)
    for a, b in zip(l1, l4):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2.1e-3)


def test_compile_cache_dir_honours_env_else_fixed_repo_path():
    from pathlib import Path

    from repro.launch.compile_cache import REPO_CACHE_DIR, compile_cache_dir

    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None
    assert compile_cache_dir({}) == REPO_CACHE_DIR
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == REPO_CACHE_DIR
    assert REPO_CACHE_DIR == Path(__file__).resolve().parents[1] / ".jax_cache"
