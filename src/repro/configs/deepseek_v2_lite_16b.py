"""deepseek-v2-lite-16b [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite].

27L, d_model=2048, 16 heads, MLA kv_lora=512 with an RMSNorm on the
latent (qk_nope 128 + qk_rope 64, v 128, no q_lora), YaRN RoPE (factor
40 over 4096 original positions, mscale 0.707 on every dimension, so
the softmax scale is 192^-0.5 · mscale(40, 0.707)²; cos and sin
unscaled, the source's ``mscale`` and ``mscale_all_dim`` being equal).  MoE: 64 routed
experts of width 1408, softmax router, greedy top-6 with the gates not
renormalised (``norm_topk_prob: false``, ``routed_scaling_factor`` 1),
2 shared experts, no dropped tokens; the sequence-wise balance loss
(``seq_aux``) at α 0.001, the upstream config's ``aux_loss_alpha``.
First layer dense (d_ff 10944); vocab 102400, head untied.  Full
attention → long_500k skipped.
"""
from repro.configs import FULL_ATTN_SHAPES
from repro.models.base import ModelConfig

#: the published model's MoE, MLA and YaRN settings, shared by the smoke
#: config
_PUBLISHED = dict(
    family="moe", first_dense_layers=1, capacity_factor=0.0,
    norm_topk_prob=False, aux_loss_alpha=0.001, rope_theta=1e4,
    yarn_factor=40.0, yarn_original=4096, yarn_mscale=0.707, norm_eps=1e-6,
)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=10944, moe_d_ff=1408, n_experts=64, experts_per_token=6,
    n_shared_experts=2,
    mla_kv_lora=512, mla_qk_nope=128, mla_qk_rope=64, mla_v_dim=128,
    vocab=102400, **_PUBLISHED,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
    d_ff=128, moe_d_ff=32, n_experts=8, experts_per_token=2,
    n_shared_experts=1,
    mla_kv_lora=32, mla_qk_nope=16, mla_qk_rope=8, mla_v_dim=16,
    vocab=256, **_PUBLISHED,
)

SHAPES = FULL_ATTN_SHAPES
