"""zamba2's published structure on the program's own terms: the decode
program's layout, the one-token Mamba2 update against the recurrence and
the chunked scan, the update's kernel, and the state's lane layout.

The preset is zamba2-7b's reduced config: two shared blocks used in turn,
two B/C groups, a concat input and hybrid uses at the uneven layers 1, 4
and 5 of 7.  The comparison of its logits with the plain float32
reference, `bench/lib/reference_zamba2.py`, lies with the benchmark's own
tests (`bench/tests/test_hybrid.py`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.mamba_scan.layout import (
    heads_per_row,
    pack_state,
    state_shape,
    unpack_state,
)
from repro.kernels.mamba_scan.ops import mamba_chunk_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.models import build_model
from repro.models.ssm import ssm_step


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("zamba2-7b").reduced()
    assert (cfg.num_mem_blocks, cfg.ssm_ngroups, cfg.concat_embed) == (2, 2, True)
    assert cfg.hybrid_layer_ids == (1, 4, 5) and cfg.n_layers == 7
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    return model, params, tokens


def test_decode_program_has_no_cond(tiny):
    """The shared blocks sit between the layer scans, not in a `cond`, and
    K/V is held for the shared blocks' three uses only."""
    model, params, tokens = tiny
    cache = jax.eval_shape(lambda: model.init_cache(batch=2, s_max=16, dtype=jnp.bfloat16))
    jaxpr = jax.make_jaxpr(model.decode_step)(
        params, tokens[:, :1], cache, jnp.asarray(3, jnp.int32))
    assert "cond[" not in str(jaxpr)
    assert str(jaxpr).count("scan[") == len(model.cfg.hybrid_layer_ids) + 1
    assert cache["kv"].k.shape[0] == 3 and cache["mamba"].state.shape[0] == 7


def _ssm_operands(key, B=2, T=6, H=8, G=2, N=16, P=16):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    s0 = jax.random.normal(ks[5], (B, H, N, P))
    return x, dt, A, Bm, Cm, s0


@pytest.mark.parametrize("hp", [1, 2, 4])
def test_one_token_update_matches_recurrence_and_chunked_scan(hp):
    """`ssm_step`, token after token from a nonzero state held ``hp``
    heads a row, against the sequential reference recurrence and the
    chunked scan (reference and interpret-mode kernel) from the same
    state; f32 throughout, so the three agree to rounding (1e-5 of values
    of order 1 to 10)."""
    x, dt, A, Bm, Cm, s0 = _ssm_operands(jax.random.PRNGKey(3))
    H, G = x.shape[2], Bm.shape[2]
    D = jax.random.normal(jax.random.PRNGKey(4), (H,))
    s, ys = pack_state(s0, hp), []
    assert s.shape == (2, H // hp, 16, 16 * hp)
    for t in range(x.shape[1]):
        y, s = ssm_step(s, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    y_seq, s_seq = mamba_scan_ref(x, dt, A, Bm, Cm, initial_state=s0)
    y_seq = y_seq + D[:, None] * x
    np.testing.assert_allclose(y_step, y_seq, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(unpack_state(s, hp), s_seq, atol=1e-4, rtol=1e-5)
    for interpret in (None, True):
        y_chunk, s_chunk = mamba_chunk_scan(x, dt, A, Bm, Cm, chunk=8,
                                            initial_state=pack_state(s0, hp),
                                            heads_per_row=hp, interpret=interpret)
        np.testing.assert_allclose(y_step, y_chunk + D[:, None] * x, atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(s, s_chunk, atol=1e-4, rtol=1e-5)
    # each head reads its own group: with the groups made equal, heads of
    # the other group see the change
    y1, _ = ssm_step(pack_state(s0, hp), x[:, 0], dt[:, 0], A,
                     Bm[:, 0].at[:, 1].set(Bm[:, 0, 0]), Cm[:, 0], D)
    hpg = H // G
    assert jnp.allclose(y1[:, :hpg], y_step[:, 0, :hpg])
    assert not jnp.allclose(y1[:, hpg:], y_step[:, 0, hpg:])


def test_step_kernel_updates_its_row_in_place_as_the_recurrence():
    """The decode update's Pallas kernel (interpret mode) on one row of a
    stacked state, against `ssm_step`: the same y and new state in that
    row, every other row untouched."""
    from repro.kernels.mamba_scan.step import mamba_step

    x, dt, A, Bm, Cm, s0 = _ssm_operands(jax.random.PRNGKey(5), H=16, P=64)
    hp, H = 2, x.shape[2]
    D = jax.random.normal(jax.random.PRNGKey(6), (H,))
    stack = jnp.stack([pack_state(s0, hp), 2.0 * pack_state(s0, hp), -pack_state(s0, hp)])
    y_want, s_want = ssm_step(stack[1], x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    y, out = mamba_step(stack, 1, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D,
                        interpret=True)
    np.testing.assert_allclose(y, y_want, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(out[1], s_want, atol=1e-4, rtol=1e-5)
    np.testing.assert_array_equal(out[0], stack[0])
    np.testing.assert_array_equal(out[2], stack[2])


@pytest.mark.parametrize("H,P,G,hp", [(112, 64, 2, 2), (32, 80, 1, 1), (6, 64, 2, 1),
                                      (8, 32, 1, 4), (8, 64, 8, 1)])
def test_state_layout_packs_heads_within_a_group(H, P, G, hp):
    """Heads fill a 128-lane row where they divide it and each group's
    heads (zamba2-7b's 112 heads of 64 in 2 groups: pairs); elsewhere one
    a row, so that no row spans two groups.  Packing is a permutation."""
    assert heads_per_row(H, P, G) == hp
    assert state_shape(3, H, 16, P, G) == (3, H // hp, 16, hp * P)
    s = jax.random.normal(jax.random.PRNGKey(7), (3, H, 16, P))
    packed = pack_state(s, hp)
    assert packed.shape == state_shape(3, H, 16, P, G)
    np.testing.assert_array_equal(unpack_state(packed, hp), s)
    if hp > 1:   # head k of a row in lanes [k·P, (k+1)·P)
        np.testing.assert_array_equal(packed[:, 0, :, P:2 * P], s[:, 1])
