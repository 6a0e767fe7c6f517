"""deepseek-v2-lite [moe] — MLA kv_lora=512 with no query compression,
2 shared + 64 routed experts top-6, YaRN x40 [arXiv:2405.04434; hf
deepseek-ai/DeepSeek-V2-Lite config.json].

27L, d_model=2048, 16 heads (MLA: qk 128 nope + 64 rope, v 128,
kv_lora_rank=512, q_lora_rank=None), expert d_ff=1408, vocab=102400,
untied head.  The first layer is a dense-FFN MLA block (d_ff=10944),
layers 2..27 are MoE with softmax gates that are not renormalised
(norm_topk_prob false; routed_scaling_factor 1, so the gates are
not scaled) — layout prefix +
26-repeat period.  Rotary positions use YaRN over 4,096 original
positions (factor 40, beta_fast 32, beta_slow 1, mscale 0.707 on both).
"""

from repro.config import (
    LayerDesc, LayerLayout, MLAConfig, MemComConfig, MoEConfig, ModelConfig,
    YarnScaling,
)


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        layout=LayerLayout(
            prefix=(LayerDesc("mla", "dense"),),
            period=(LayerDesc("mla", "moe"),),
            repeats=26,
        ),
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,  # dense first-layer FFN
        vocab_size=102400,
        mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                      num_shared_experts=2, shared_d_ff=1408,
                      norm_topk_prob=False),
        rope_theta=10_000.0,
        rope_scaling=YarnScaling(factor=40.0,
                                 original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=0.707, mscale_all_dim=0.707),
        tie_embeddings=False,
        max_seq=163_840,
        memcom=MemComConfig(num_memory_tokens=768),
        source="[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]",
    )


def smoke_config() -> ModelConfig:
    m = config().moe
    return config().replace(
        name="deepseek-v2-lite-smoke",
        layout=LayerLayout(
            prefix=(LayerDesc("mla", "dense"),),
            period=(LayerDesc("mla", "moe"),),
            repeats=2,
        ),
        d_model=64, num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=512,
        mla=MLAConfig(kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(num_experts=16, top_k=3, expert_d_ff=32,
                      num_shared_experts=2, shared_d_ff=32,
                      norm_topk_prob=m.norm_topk_prob),
        rope_scaling=YarnScaling(
            factor=40.0, original_max_position_embeddings=64, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        max_seq=512, memcom=MemComConfig(num_memory_tokens=8),
        dtype="float32", source="reduced smoke",
    )
