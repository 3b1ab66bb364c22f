"""The FLOP and byte functions against counts made by hand."""
from __future__ import annotations

import json

from bench.lib import flops
from bench.tests.conftest import DATA, ROOT

TINY = json.load(open(DATA / "tiny-dense.json"))["run"]      # d 128, 4/2 heads of 32, ff 256
STABLELM = json.load(open(ROOT / "bench/configs/stablelm-3b.json"))["run"]
QWEN3 = json.load(open(ROOT / "bench/configs/qwen3-14b.json"))["run"]


def test_dense_counts_by_hand():
    # attention 128*4*32*2 + 128*2*32*2 = 49152; MLP 3*128*256 = 98304;
    # two layers and a 128 x 256 head
    assert flops.matmul_params(TINY) == 2 * (49152 + 98304) + 128 * 256 == 327680
    assert flops.token_flops(TINY, context=10) == 2 * 327680 + 4 * 2 * 4 * 32 * 10
    # prompt of 3: 3 tokens through the layers, triangle 1+2+3, head once
    assert flops.prefill_flops(TINY, 3) == (2 * 294912 * 3 + 4 * 2 * 4 * 32 * 6
                                            + 2 * 128 * 256)
    assert flops.served_flops(TINY, 3, 1) == flops.prefill_flops(TINY, 3)
    assert flops.served_flops(TINY, 3, 2) == (flops.prefill_flops(TINY, 3)
                                              + flops.token_flops(TINY, 4))


def test_stablelm_sizes():
    # 2.795 B parameters with the 50304 x 2560 embedding table
    body = flops.matmul_params(STABLELM) + 50304 * 2560
    assert body == 32 * (4 * 2560 * 2560 + 3 * 2560 * 6912) + 2 * 50304 * 2560 == 2_795_110_400
    # decode step of 16 sequences with 769 cache slots: 5.33 GB of weights
    # read (the embedding table is only gathered) and 4.03 GB of cache
    assert flops.weight_bytes_read(STABLELM) == 2 * (body - 50304 * 2560)
    assert flops.cache_bytes(STABLELM, 16, 769) == 2 * 32 * 16 * 769 * 32 * 80 * 2
    assert flops.decode_step_bytes(STABLELM, 16, 769, 1) == (
        flops.weight_bytes_read(STABLELM) + flops.cache_bytes(STABLELM, 16, 769))


def test_qwen3_sizes():
    # 14.77 B parameters (29.54 GB in bf16): per layer q, o 5120 x 5120,
    # k, v 5120 x 1024, MLP 3 x 5120 x 17408; head and embedding 5120 x 151936
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
    assert flops.matmul_params(QWEN3) == 40 * layer + 5120 * 151936 == 13_989_969_920
    # a chip of the 1x4 mesh reads a quarter of the weights and of 32
    # sequences' cache of 769 slots (8 KV heads of 128)
    cache = 2 * 40 * 32 * 769 * 8 * 128 * 2
    assert flops.cache_bytes(QWEN3, 32, 769) == cache
    assert flops.decode_step_bytes(QWEN3, 32, 769, 4) == (2 * 13_989_969_920 + cache) / 4


def test_kernel_counts_by_hand():
    # causal over 4 tokens: 10 pairs; q, o for 2 heads, k, v for 1
    assert flops.flash(1, 2, 1, 4, 8) == (4 * 2 * 8 * 10, 2 * 4 * 8 * (2 * 2 + 2 * 1))
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert flops.roofline_s(1000, 50, peak) == 10.0      # compute-bound
    assert flops.roofline_s(100, 500, peak) == 50.0      # memory-bound
