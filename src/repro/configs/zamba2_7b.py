"""zamba2-7b [hybrid]: Mamba2 layers with two shared transformer blocks.
[arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct config.json]

Published widths: d_model 3584; Mamba2 with 112 heads of 64, d_state 64,
2 groups of B/C, d_inner 7168, conv 4.  At 13 of its 81 layers
(``hybrid_layer_ids``) a shared block, one of two used in turn, reads the
stream concatenated with the token embedding (7168 wide): RMSNorm, MHA of
32 heads of 224 with rotary embedding and scale (head_dim / 2) ** -0.5,
RMSNorm, a gated GELU MLP of 14336 with that use's LoRA (rank 128) on its
gate and up projections; the use's own ``linear`` (3584 x 3584) adds the
result to that layer's Mamba2 input.

The program registers the first 27 layers (0-26, hybrid uses at 6, 11, 17
and 23): the first of three pipeline stages of a bf16 deployment over
three chips, each stage on one chip.  Later stages' layers are left out.
"""
from repro.configs.base import ArchConfig, register

PUBLISHED_HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)
N_LAYERS = 27

CONFIG = register(
    ArchConfig(
        name="zamba2-7b",
        family="hybrid",
        n_layers=N_LAYERS,
        d_model=3584,
        n_heads=32,
        n_kv_heads=32,
        d_ff=14336,
        vocab_size=32000,
        head_dim=224,
        rope_theta=10000.0,
        attn_scale=(224 / 2) ** -0.5,
        ssm_state=64,
        ssm_head_dim=64,
        ssm_expand=2,
        ssm_conv=4,
        ssm_ngroups=2,
        ssm_dt_min=0.001,
        hybrid_layer_ids=tuple(i for i in PUBLISHED_HYBRID_LAYER_IDS if i < N_LAYERS),
        num_mem_blocks=2,
        adapter_rank=128,
        concat_embed=True,
        norm_eps=1e-5,
    )
)
