"""One-token Mamba2 state update as a Pallas kernel, in place on the stack.

The decode step's recurrence, s ← exp(dt·A)·s + dt·(B ⊗ x) and
y = C·s + D·x, on one layer's row of the stacked state (L, B, R, N, W):
R rows of hp heads side by side in W = hp·P lanes (`layout`).  The
layer is a scalar-prefetch operand that only the index maps read, so each
grid step DMAs its block of rows straight out of the stacked buffer and
writes the new state back over it (the state output aliases the input).
The state is read once and written once; XLA's fusions of the same
update read it twice, once for the new state and once for y.

Grid = (B, G): one sequence and one B/C group a step, the group's R/G
rows the block.  B and C arrive spread over the lanes, (B, G, N, W), so a
row's outer product and its sum over N take no transpose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import VMEM_LIMIT_BYTES
from repro.kernels.mamba_scan.layout import over_lanes


def _step_kernel(s_ref, st_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, so_ref):
    x = x_ref[...].astype(jnp.float32)           # (rows, W)
    dt = dt_ref[...]
    decay = jnp.exp(dt * a_ref[...])
    new = (st_ref[...] * decay[:, None, :]
           + b_ref[...].astype(jnp.float32)[None] * (dt * x)[:, None, :])
    so_ref[...] = new
    y_ref[...] = (jnp.sum(new * c_ref[...].astype(jnp.float32)[None], axis=1)
                  + d_ref[...] * x).astype(y_ref.dtype)


def mamba_step_pallas(state, layer, x, dt, A, D, Bl, Cl, *, interpret: bool = False):
    """state (L, B, R, N, W) f32, the stack; x, dt (B, G, R/G, W), dt
    through softplus, each head's value over its P lanes; A, D (G, R/G, W)
    likewise; Bl, Cl (B, G, N, W), each group's B and C spread over the
    lanes.  Returns (y (B, G, R/G, W) f32, the stack with row ``layer``
    updated in place)."""
    L, Bsz, R, N, W = state.shape
    G = Bl.shape[1]
    rows = R // G
    scalars = jnp.asarray(layer, jnp.int32).reshape(1)
    row = pl.BlockSpec((None, None, rows, W), lambda b, g, s: (b, g, 0, 0))
    lane = pl.BlockSpec((None, rows, W), lambda b, g, s: (g, 0, 0))
    grp = pl.BlockSpec((None, None, N, W), lambda b, g, s: (b, g, 0, 0))
    st = pl.BlockSpec((None, None, rows, N, W), lambda b, g, s: (s[0], b, g, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Bsz, G),
            in_specs=[st, row, row, lane, lane, grp, grp],
            out_specs=[row, st]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="goldyloc_ssm_step",
    )(scalars, state, x, dt, A, D, Bl, Cl)


def mamba_step(state, layer, x, dt, A, Bm, Cm, D, *, interpret: bool = False):
    """`mamba_step_pallas` on `ssm_step`'s operands (`models/ssm.py`): x
    (B,H,P); dt (B,H); A, D (H,); Bm, Cm (B,G,N).  Returns (y (B,H,P) f32,
    the stack with row ``layer`` updated)."""
    L, Bsz, R, N, W = state.shape
    H, P, G, f32 = x.shape[1], x.shape[2], Bm.shape[1], jnp.float32

    def lanes(t):   # (..., H) -> (..., G, R/G, W)
        return over_lanes(t, R, P).reshape(*t.shape[:-1], G, R // G, W)

    def spread(t):  # (B,G,N) -> (B,G,N,W)
        return jnp.broadcast_to(t.astype(f32)[..., None], (*t.shape, W))

    y, state = mamba_step_pallas(
        state, layer, x.astype(f32).reshape(Bsz, G, R // G, W), lanes(dt), lanes(A),
        lanes(D), spread(Bm), spread(Cm), interpret=interpret)
    return y.reshape(Bsz, H, P), state
