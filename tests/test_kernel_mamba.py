"""Mamba2 chunked-scan kernel vs sequential oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tuner import SCAN_TILES
from repro.kernels.mamba_scan import (
    mamba_chunk_ref,
    mamba_chunk_scan,
    mamba_scan_ref,
)
from repro.kernels.mamba_scan import ops as ssd_ops
from repro.kernels.mamba_scan.ops import ssd_scan
from repro.kernels.mamba_scan.ref import ssd_chunk_ref


def _inputs(key, B, T, H, P, N, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, T, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = (jax.random.normal(ks[3], (B, T, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(jax.random.fold_in(key, 9), (B, T, N)) * 0.5).astype(dtype)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("B,T,H,P,N", [(2, 200, 3, 32, 16), (1, 128, 2, 64, 64)])
def test_chunk_scan_matches_sequential(B, T, H, P, N, chunk):
    x, dt, A, Bm, Cm = _inputs(jax.random.PRNGKey(T + chunk), B, T, H, P, N)
    y_ref, S_ref = mamba_scan_ref(x, dt, A, Bm, Cm)
    y, S = mamba_chunk_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(y, y_ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(S, S_ref, rtol=3e-4, atol=3e-4)


def test_state_continuation():
    """Splitting a sequence and chaining states == one pass (decode basis)."""
    x, dt, A, Bm, Cm = _inputs(jax.random.PRNGKey(1), 2, 160, 2, 16, 8)
    y_ref, S_ref = mamba_scan_ref(x, dt, A, Bm, Cm)
    y1, S1 = mamba_chunk_ref(x[:, :96], dt[:, :96], A, Bm[:, :96], Cm[:, :96], chunk=32)
    y2, S2 = mamba_chunk_ref(
        x[:, 96:], dt[:, 96:], A, Bm[:, 96:], Cm[:, 96:], chunk=32, initial_state=S1
    )
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(S2, S_ref, rtol=3e-4, atol=3e-4)


def test_scan_vjp_matches_oracle():
    x, dt, A, Bm, Cm = _inputs(jax.random.PRNGKey(2), 1, 96, 2, 16, 8)
    f = lambda *a: mamba_chunk_scan(*a, chunk=32, interpret=True)[0].sum()
    fr = lambda *a: mamba_scan_ref(*a)[0].sum()
    g = jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    gr = jax.grad(fr, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


def test_decay_stability_long_sequence():
    """No NaN/inf over long sequences with strong decay."""
    x, dt, A, Bm, Cm = _inputs(jax.random.PRNGKey(3), 1, 1024, 2, 16, 8)
    A = A * 10.0  # strong decay
    y, S = mamba_chunk_ref(x, dt, A, Bm, Cm, chunk=128)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(S).all())


def _general_inputs(key, B, T, H, P, N):
    ks = jax.random.split(key, 5)
    xd = jax.random.normal(ks[0], (B, T, H, P))
    da = -jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    Bm = jax.random.normal(ks[2], (B, T, H, N)) * 0.5
    Cm = jax.random.normal(ks[3], (B, T, H, N)) * 0.5
    S0 = jax.random.normal(ks[4], (B, H, N, P))
    return xd, da, Bm, Cm, S0


@pytest.mark.parametrize("chunk", [t.bm for t in SCAN_TILES])
def test_ssd_kernel_matches_chunk_ref_every_scan_tile(chunk):
    """The kernel (column decay layout, matmul prefix sum) agrees with the
    chunked oracle at every chunk length the tuner can pick."""
    xd, da, Bm, Cm, _ = _general_inputs(jax.random.PRNGKey(chunk), 1, 1100,
                                        2, 16, 8)
    y_ref, S_ref = ssd_chunk_ref(xd, da, Bm, Cm, chunk=chunk)
    y, S = ssd_scan(xd, da, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(y, y_ref, rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(S, S_ref, rtol=5e-5, atol=5e-5)


def test_ssd_kernel_takes_initial_state(monkeypatch):
    """``initial_state`` feeds the kernel's state input: the forward never
    runs the reference, and matches it with the same carried state."""
    xd, da, Bm, Cm, S0 = _general_inputs(jax.random.PRNGKey(5), 2, 160, 2,
                                         16, 8)
    y_ref, S_ref = ssd_chunk_ref(xd, da, Bm, Cm, chunk=32, initial_state=S0)

    def no_ref(*a, **k):
        raise AssertionError("forward fell back to the reference")

    monkeypatch.setattr(ssd_ops, "ssd_chunk_ref", no_ref)
    y, S = ssd_scan(xd, da, Bm, Cm, chunk=32, initial_state=S0,
                    interpret=True)
    np.testing.assert_allclose(y, y_ref, rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(S, S_ref, rtol=5e-5, atol=5e-5)


def test_ssd_kernel_initial_state_grad():
    xd, da, Bm, Cm, S0 = _general_inputs(jax.random.PRNGKey(6), 1, 96, 2,
                                         16, 8)
    f = lambda s0: ssd_scan(xd, da, Bm, Cm, chunk=32, initial_state=s0,
                            interpret=True)[0].sum()
    fr = lambda s0: ssd_chunk_ref(xd, da, Bm, Cm, chunk=32,
                                  initial_state=s0)[0].sum()
    np.testing.assert_allclose(jax.grad(f)(S0), jax.grad(fr)(S0),
                               rtol=1e-4, atol=1e-4)
