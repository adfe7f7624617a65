"""DeepSeek-V2-Lite — multi-head latent attention, 64 routed experts top-6
plus 2 shared, one leading dense layer [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite]. The 2 shared experts are one ungated SwiGLU
of width 2 x 1408; YaRN's published `mscale` equals `mscale_all_dim`, so
the rotary cos/sin keep their scale of one."""
from repro.configs import ArchConfig, register

register(ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,               # the leading dense layer's SwiGLU
    vocab_size=102400,
    mlp_type="swiglu",
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_mscale_all_dim=0.707,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    top_k=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    shared_expert_d_ff=2816,
    shared_expert_gate=False,
    norm_topk_prob=False,
    seq_aux=True,
    aux_loss_alpha=0.001,
    first_k_dense=1,
    source="arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite",
))
