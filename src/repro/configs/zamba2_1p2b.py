"""zamba2-1.2b [hybrid]: Mamba2 layers with a shared transformer block.
[arXiv:2411.15242; hf Zyphra/Zamba2-1.2B]

The same layer equations as zamba2-7b (`configs/zamba2_7b.py`): the
shared block reads [stream ‖ embedding], its MLP carries a per-use LoRA,
and each use's ``linear`` feeds the next Mamba2 layer's input.

Unchecked: the model's ``config.json`` is not at hand, so these values
come from the paper and the family's convention, not from the file:
d_model 2048, 38 layers, one shared block, hybrid uses at every sixth
layer (5, 11, ..., 35), attention heads of 128 (2 x 2048 / 32), MLP 8192,
one B/C group, adapter rank 128, no adapters on q/k/v.
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(
    ArchConfig(
        name="zamba2-1.2b",
        family="hybrid",
        n_layers=38,
        d_model=2048,
        n_heads=32,
        n_kv_heads=32,
        d_ff=8192,
        vocab_size=32000,
        head_dim=128,
        attn_scale=(128 / 2) ** -0.5,
        ssm_state=64,
        ssm_head_dim=64,
        ssm_expand=2,
        ssm_conv=4,
        ssm_ngroups=1,
        ssm_dt_min=0.001,
        hybrid_layer_ids=(5, 11, 17, 23, 29, 35),
        num_mem_blocks=1,
        adapter_rank=128,
        concat_embed=True,
    )
)
