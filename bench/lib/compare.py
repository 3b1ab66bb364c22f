"""The comparisons that decide `correct`.

- `token_gaps`: for a served model.  At each position whose token was
  served, how far the served token's reference logit lies below the
  reference's best logit there.  A token the program chose by a wrong
  computation lands anywhere in the reference's ranking; one chosen
  right differs from the reference's first choice only where two logits
  lie within rounding of each other.
- `gemm_error`: for answers that can be checked one by one, the largest
  absolute error of a result over the largest magnitude of its reference.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def token_gaps(ref_logits, served) -> np.ndarray:
    """``ref_logits`` (n, L, V) at the positions that chose the served
    tokens; ``served`` (n, L) with -1 where a row served fewer tokens.
    Returns the gaps of every served token, flattened."""
    ref = jnp.asarray(ref_logits, jnp.float32)
    tok = jnp.asarray(np.maximum(served, 0))
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
    gaps = np.asarray(best - got)
    return gaps[np.asarray(served) >= 0]


def argmax_tokens(logits) -> np.ndarray:
    return np.asarray(jnp.argmax(jnp.asarray(logits), axis=-1))


def gemm_error(out, ref) -> float:
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
