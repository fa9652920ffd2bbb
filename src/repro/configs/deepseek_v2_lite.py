"""deepseek-v2-lite [moe] — MLA without a query low-rank (kv_lora 512),
YaRN rotary scaling, one leading dense layer, then 26 layers of 64
routed experts (top-6, softmax gates not renormalised) and 2 shared.
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]

``SHARE`` is what one chip holds when each layer is split over 8 chips
by expert parallelism: experts 0-7 of each expert layer (the router
keeps its 64 outputs) and vocabulary rows 0-12,799, at a depth of the
dense layer and 4 expert layers (the other 22 lie on further pipeline
stages).  Every width is the published one; weights are float32.
"""
import dataclasses

import jax.numpy as jnp

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnConfig

SOURCE = ("https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
          "config.json")

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,             # MLA: one latent, per-head keys and values
    d_ff=10944,                  # the leading dense layer's SwiGLU width
    vocab_size=102400,
    head_dim=192,                # qk_nope(128) + qk_rope(64)
    block_pattern=("attn",),
    norm="rmsnorm",
    norm_eps=1e-6,
    ffn="swiglu",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    first_k_dense=1,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  expert_d_ff=1408, router_aux_loss=0.001,
                  renormalize=False),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, qk_rope_head_dim=64,
                  qk_nope_head_dim=128, v_head_dim=128),
    source=SOURCE,
)

SHARE = dataclasses.replace(
    CONFIG,
    name="deepseek-v2-lite-ep8",
    num_layers=5,
    vocab_size=12800,
    moe=dataclasses.replace(CONFIG.moe, experts_held=8),
    param_dtype=jnp.float32,
)
