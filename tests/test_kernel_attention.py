"""Flash-attention kernel vs dense oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_ref, mha_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _qkv(key, B, Hq, Hkv, T, S, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, T, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32) * 0.5
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
@pytest.mark.parametrize("B,Hq,Hkv,T,S,D", [(1, 4, 2, 256, 256, 64), (2, 2, 1, 130, 250, 32)])
def test_flash_matches_oracle(B, Hq, Hkv, T, S, D, causal, window, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(T + S), B, Hq, Hkv, T, S, D, dtype)
    off = S - T if causal else 0
    ref = mha_ref(q, k, v, causal=causal, window=window, q_offset=off)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=off, interpret=True
    )
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_flash_ref_chunking_invariance():
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 4, 4, 192, 192, 32)
    ref = mha_ref(q, k, v)
    for bkv in (64, 128, 192):
        np.testing.assert_allclose(
            flash_ref(q, k, v, block_kv=bkv), ref, rtol=2e-4, atol=2e-4
        )


def test_flash_vjp_matches_oracle():
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, 2, 128, 128, 32)
    f = lambda q, k, v: (flash_attention(q, k, v, interpret=True) ** 2).sum()
    fr = lambda q, k, v: (mha_ref(q, k, v) ** 2).sum()
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_decode_single_query_against_full():
    """One-token decode (q_offset = S-1) equals last row of full attention."""
    B, H, S, D = 2, 4, 64, 32
    q, k, v = _qkv(jax.random.PRNGKey(9), B, H, H, S, S, D)
    full = mha_ref(q, k, v, causal=True)
    one = mha_ref(q[:, :, -1:], k, v, causal=True, q_offset=S - 1)
    np.testing.assert_allclose(one, full[:, :, -1:], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("L,B,Hq,Hkv,D,S,pos,window,block_bytes", [
    (3, 2, 32, 32, 80, 256, 100, 0, None),    # MHA, head size 80 (stablelm-3b)
    (2, 2, 10, 2, 128, 256, 255, 0, None),    # GQA 5:1, the last position
    (2, 1, 4, 2, 64, 384, 200, 48, None),     # sliding window
    (2, 2, 4, 2, 64, 128, 0, 0, None),        # the first token
    (2, 2, 32, 32, 80, 512, 300, 0, 2**16),   # four position steps, hd 80
    (2, 2, 10, 2, 128, 384, 130, 0, 2**15),   # three steps, GQA 5:1
    (2, 1, 4, 2, 64, 640, 600, 200, 2**14),   # five steps, window spans three
], ids=["mha-hd80", "gqa-last", "window", "first", "steps-hd80", "steps-gqa",
        "steps-window"])
def test_decode_attention_matches_oracle(monkeypatch, L, B, Hq, Hkv, D, S, pos,
                                         window, block_bytes):
    """The decode kernel (interpret mode) and its reference, on a stacked,
    positions-minor cache: each writes the token's K/V at ``pos`` of the
    layer's row and nowhere else, and attends as the dense oracle does
    over that row's positions up to ``pos``.  A small ``block_bytes``
    splits the positions over several grid steps (flash decoding)."""
    from repro.kernels.decode_attention import decode_attention_ref, kernel

    if block_bytes:
        monkeypatch.setattr(kernel, "BLOCK_BYTES", block_bytes)
        assert kernel.block_shape(Hkv, D, S, 2)[1] < S
    ks = jax.random.split(jax.random.PRNGKey(L * S + pos), 5)
    q = jax.random.normal(ks[0], (B, Hq * D), jnp.float32)
    kn, vn = (jax.random.normal(x, (B, Hkv * D)).astype(jnp.bfloat16) for x in ks[1:3])
    k, v = (jax.random.normal(x, (L, B, Hkv, D, S)).astype(jnp.bfloat16) for x in ks[3:])
    for layer in range(L):
        args = (q * D ** -0.5, kn, vn, k, v, jnp.int32(layer), jnp.int32(pos),
                jnp.int32(window))
        want_k = k.at[layer, :, :, :, pos].set(kn.reshape(B, Hkv, D))
        want_v = v.at[layer, :, :, :, pos].set(vn.reshape(B, Hkv, D))
        rows = lambda c: c[layer, ..., :pos + 1].swapaxes(-1, -2)
        want = mha_ref(q.reshape(B, Hq, 1, D).astype(jnp.bfloat16), rows(want_k),
                       rows(want_v), causal=True, window=window, q_offset=pos)
        for o, k2, v2 in (kernel.decode_attention_pallas(*args, interpret=True),
                          decode_attention_ref(*args)):
            np.testing.assert_array_equal(k2, want_k)
            np.testing.assert_array_equal(v2, want_v)
            np.testing.assert_allclose(o.reshape(B, Hq, D), want[:, :, 0].astype(jnp.float32),
                                       rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("hkv,hd,S,want", [
    (32, 80, 896, (16, 896)),      # stablelm-3b at the cell's capacity
    (2, 128, 896, (2, 896)),       # a chip's share of qwen3-14b
    (32, 80, 8192, (8, 2048)),     # stablelm-3b at 8k positions
    (8, 128, 32768, (1, 16384)),   # qwen3-14b at 32k positions
    (32, 64, 524288, (2, 16384)),  # zamba2-1.2b's shared attention at 512k
    (5, 80, 384, (5, 384)),        # no head count starts on a lane tile
])
def test_decode_block_fits_at_any_capacity(hkv, hd, S, want):
    """A grid step's K block holds at most `BLOCK_BYTES` whatever the
    cache's capacity: the positions are split over grid steps."""
    from repro.kernels.decode_attention import kernel

    hb, ts = kernel.block_shape(hkv, hd, S, 2)
    assert (hb, ts) == want
    assert S % ts == 0 and ts % kernel.TILE == 0 and hkv % hb == 0
    assert hb * hd * ts * 2 <= kernel.BLOCK_BYTES
