"""deepseek-v2-lite-16b [moe]: 27L, d=2048, 16H (GQA kv=16 slot; actual
attention is MLA kv_lora=512), expert d_ff=1408, vocab=102400.
MoE: 2 shared + 64 routed, top-6, first layer dense. [arXiv:2405.04434; hf]
"""
from .base import ArchConfig, LayerSpec, GLOBAL

_MOE = LayerSpec(mixer="attn", mlp="moe")

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    d_model=2048,
    num_layers=27,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                    # the single dense layer's FFN width
    vocab_size=102400,
    prefix_layers=(GLOBAL,),       # layer 0 is dense
    block_pattern=(_MOE,),
    num_experts=64,
    top_k=6,
    num_shared_experts=2,
    moe_d_ff=1408,
    capacity_factor=0.0,           # dropless: decode must equal full forward
    #                                (capacity drops are batch-dependent)
    mla=True,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    act="silu",
    rope_theta=10_000.0,
    tie_embeddings=False,
    source="arXiv:2405.04434; hf",
    notes="MLA compressed-KV cache pages are what Morpheus caches here",
)
