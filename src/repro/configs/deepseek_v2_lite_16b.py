"""DeepSeek-V2-Lite 16B [arXiv:2405.04434; hf] — MLA + fine-grained MoE.

27L d_model=2048 16H vocab=102400; MLA kv_lora=512 (qk_nope 128 + qk_rope 64,
v_head 128, q un-compressed in Lite); layer 0 dense FFN 10944; layers 1..26
MoE 2 shared + 64 routed top-6 (softmax scores, greedy, not renormalized),
expert d_ff=1408; YaRN rope (factor 40 over 4096 positions).  The routed
experts run dropless (``models.blocks.apply_moe``), as the published model
does.

Assignment-sheet note (DESIGN.md §5): the assignment line says both
"MoE 64e top-6" and "2 shared+160 routed"; 160 routed is DeepSeek-V2-236B.
The Lite config per arXiv:2405.04434/HF is 64 routed — implemented here.
"""
from ..models.base import MLACfg, MoECfg, ModelConfig, YarnCfg

FULL = ModelConfig(
    name="deepseek_v2_lite_16b",
    family="moe",
    vocab=102_400,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,               # unused by MLA (kept for generic paths)
    head_dim=192,                # qk_nope + qk_rope
    d_ff=1408,
    prefix_pattern=("mla",),
    block_pattern=("mla_moe",),
    n_groups=26,
    norm="rmsnorm",
    act="swiglu",
    rope_theta=10_000.0,
    # config.json "rope_scaling": type yarn, factor 40,
    # original_max_position_embeddings 4096, beta_fast 32, beta_slow 1,
    # mscale 0.707, mscale_all_dim 0.707.
    rope_scaling=YarnCfg(factor=40.0, original_max_position=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                         mscale_all_dim=0.707),
    mla=MLACfg(kv_lora=512, qk_nope=128, qk_rope=64, v_head=128),
    moe=MoECfg(
        n_routed=64, n_shared=2, top_k=6, d_ff_expert=1408,
        first_dense_ff=10944, norm_topk=False,
    ),
    source="arXiv:2405.04434 + hf:deepseek-ai/DeepSeek-V2-Lite",
)


def smoke() -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        FULL, vocab=512, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24,
        d_ff=32, n_groups=2,
        mla=MLACfg(kv_lora=32, qk_nope=16, qk_rope=8, v_head=16),
        moe=MoECfg(n_routed=8, n_shared=2, top_k=2, d_ff_expert=32,
                   first_dense_ff=128, capacity_factor=1.5),
        param_dtype="float32", dtype="float32",
    )
