"""Cost-model invariants (incl. hypothesis property tests)."""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # skips if hypothesis missing

from repro.core import (
    DEFAULT_SPEC,
    GemmDesc,
    group_time,
    isolated_time,
    kernel_stats,
    sequential_time,
)
from repro.kernels.gemm.ops import TileConfig

TILE = TileConfig(256, 256, 256)


def test_bigger_tiles_reduce_traffic():
    d = GemmDesc(4096, 4096, 4096)
    small = kernel_stats(d, TileConfig(128, 128, 128))
    big = kernel_stats(d, TileConfig(512, 512, 128))
    assert big.hbm_bytes < small.hbm_bytes
    assert big.n_tiles < small.n_tiles


def test_group_beats_sequential_for_small_gemms():
    """Launch amortization + bubble filling: the paper's core opportunity."""
    d = GemmDesc(512, 512, 512)
    members = [(d, TileConfig(128, 128, 128))] * 4
    assert group_time(members) < sequential_time(members)


def test_contention_hurts_large_working_sets():
    """Aggregate VMEM overflow must be able to make concurrency lose."""
    d = GemmDesc(4096, 4096, 20480)
    t = TileConfig(512, 512, 512)
    members = [(d, t)] * 16
    assert group_time(members) > sequential_time(members) * 0.9


def test_rc_spec_scaling():
    spec2 = DEFAULT_SPEC.scaled(0.5)
    assert spec2.vmem_bytes == DEFAULT_SPEC.vmem_bytes // 2
    assert spec2.hbm_bw == DEFAULT_SPEC.hbm_bw / 2
    d = GemmDesc(2048, 2048, 2048)
    assert isolated_time(d, TILE, spec2) >= isolated_time(d, TILE)


def test_panel_residency_reduces_traffic():
    d = GemmDesc(2048, 2048, 8192)
    t = TileConfig(256, 256, 256)
    full = kernel_stats(d, t, vmem_budget=DEFAULT_SPEC.vmem_bytes)
    tiny = kernel_stats(d, t, vmem_budget=2 * 2**20)
    assert full.a_resident and not tiny.a_resident
    assert full.hbm_bytes < tiny.hbm_bytes


@settings(max_examples=50, deadline=None)
@given(
    m=st.sampled_from([128, 512, 2048, 8192]),
    n=st.sampled_from([128, 512, 2048, 8192]),
    k=st.sampled_from([64, 512, 4096, 20480]),
    bm=st.sampled_from([64, 128, 256, 512]),
    bn=st.sampled_from([128, 256, 512]),
    cd=st.sampled_from([1, 2, 4, 8, 16]),
)
def test_time_properties(m, n, k, bm, bn, cd):
    d = GemmDesc(m, n, k)
    t = TileConfig(bm, bn, 128)
    iso = isolated_time(d, t)
    assert np.isfinite(iso) and iso > 0
    grp = group_time([(d, t)] * cd)
    seq = sequential_time([(d, t)] * cd)
    assert np.isfinite(grp) and grp > 0
    # grouped can never beat the merged roofline by construction
    st_ = kernel_stats(d, t, vmem_budget=DEFAULT_SPEC.vmem_bytes // cd)
    lower = max(
        cd * st_.flops / (DEFAULT_SPEC.peak(d.dtype) * st_.mxu_util),
        cd * st_.hbm_bytes / DEFAULT_SPEC.hbm_bw,
    )
    assert grp >= lower * 0.999
    # sequential is never faster than one member alone
    assert seq >= iso * 0.999


def test_device_spec_table_keyed_by_device_kind():
    from types import SimpleNamespace

    from repro.core.cost_model import CHIP_SPECS, device_spec

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_spec(v5e) is CHIP_SPECS["TPU v5 lite"] is DEFAULT_SPEC
    assert device_spec(SimpleNamespace(platform="cpu", device_kind="cpu")) \
        is DEFAULT_SPEC
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        device_spec(SimpleNamespace(platform="tpu",
                                    device_kind="TPU v9 imaginary"))
