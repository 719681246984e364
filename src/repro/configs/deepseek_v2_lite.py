"""DeepSeek-V2-Lite 16B (2.4B active)
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json].

27 layers at d_model=2048: the first is dense (``first_k_dense_replace`` 1,
SwiGLU of 10944), so ``n_layers`` counts the 26 MoE layers of the period
stack and ``first_k_dense`` the one ahead of it.  16 heads of MLA without
q-LoRA (kv_lora=512, qk_nope=128, qk_rope=64, v=128) under YaRN rope
(factor 40 over 4096 positions, mscale 0.707).  MoE: 64 routed experts of
width 1408, top-6 softmax scores without renormalisation, 2 shared experts,
DeepSeek's per-sequence balance loss.  vocab=102400, untied head.  The
balance loss weight (``aux_loss_alpha``) is not in the config: 0.001,
DeepSeek-V2's.
"""

from repro.models import (AttentionConfig, LayerSpec, MLAConfig, ModelConfig,
                          MoEConfig)
from repro.models.rotary import YaRN

ARCH_ID = "deepseek-v2-lite-16b"
SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        n_layers=26,
        first_k_dense=1,
        d_model=2048,
        vocab_size=102400,
        d_ff=10944,
        attn=AttentionConfig(
            n_heads=16, n_kv_heads=16, head_dim=128, rope_theta=10000.0,
            mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128,
                          qk_rope_dim=64, v_head_dim=128),
            rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                              beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                              mscale_all_dim=0.707)),
        moe=MoEConfig(n_experts=64, n_routed=64, top_k=6, d_ff=1408,
                      n_shared_experts=2, score_fn="softmax",
                      norm_topk_prob=False, aux_loss="seq",
                      aux_loss_weight=0.001),
        pattern=(LayerSpec(kind="attn", mlp="moe"),),
        norm_eps=1e-6,
        source=SOURCE,
    )
