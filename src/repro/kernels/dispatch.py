"""Backend dispatch: pallas kernels on TPU, reference (XLA) path elsewhere.

The dry-run lowers the XLA reference path (collective structure is identical;
see DESIGN.md §9).  Tests force ``interpret=True`` explicitly.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax

# Scoped VMEM every kernel may use: the budget the cost model plans tiles
# against (`TPUSpec.vmem_bytes`).  Mosaic's default scoped limit on v5e is
# 16 MiB, which refuses the deepest split-K reduce block in the tile space.
VMEM_LIMIT_BYTES = 32 * 2**20

_FORCED: bool | None = None


def use_pallas() -> bool:
    """True when pallas kernels should be used for the hot paths."""
    if _FORCED is not None:
        return _FORCED
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """interpret=True whenever we are not on a real TPU."""
    return jax.default_backend() != "tpu"


@contextmanager
def force_pallas(enabled: bool = True):
    global _FORCED
    prev, _FORCED = _FORCED, enabled
    try:
        yield
    finally:
        _FORCED = prev
