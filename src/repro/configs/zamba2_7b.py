"""zamba2-7b [hybrid] — 81 Mamba2 layers, each with its own mixer; 13 of
them (``hybrid_layer_ids`` 6, 11, 17, 23, ... 77: gaps of 5 and 6) first
invoke one of two shared attention+MLP blocks, in turn (invocation k uses
block k % 2), over the residual concatenated with the token embeddings.
Invocation k's output goes through a rank-128 LoRA adapter of its own on the
MLP's gate/up projection and the layer's own 3584x3584 ``linear``, and is
added to the input of that layer's Mamba2 mixer (not to the residual).

Mamba2: 112 heads x 64, 2 groups of B/C, d_state 64, chunk 256, gated
RMSNorm per group.  Shared attention: 7168 wide in, 32 heads x 224 (no GQA),
RoPE theta 1e4, softmax scale (224/2)^-0.5, o_proj 7168 -> 3584; exact-erf
GeGLU 14336 wide.  Tied head.
[arXiv:2411.15242; https://huggingface.co/Zyphra/Zamba2-7B-Instruct]"""
from repro.configs.base import ModelConfig, SSMConfig, register

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


@register("zamba2-7b")
def zamba2_7b() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b",
        arch_type="hybrid",
        num_layers=81,
        d_model=3584,
        num_heads=32,
        num_kv_heads=32,
        head_dim=224,                 # attention_head_dim: 7168 / 32
        d_ff=14336,                   # shared blocks' MLP
        vocab_size=32000,
        act="gelu_exact",
        rope_theta=1e4,
        rms_eps=1e-5,
        tie_embeddings=True,
        hybrid_layer_ids=HYBRID_LAYER_IDS,
        num_mem_blocks=2,
        adapter_rank=128,
        # d_inner = 2 * d_model = 7168 = 112 heads x 64
        ssm=SSMConfig(state_dim=64, num_heads=112, head_dim=64,
                      conv_width=4, chunk_size=256, expand=2, n_groups=2,
                      dt_min=1e-3),    # time_step_min: the forward clamps dt
        source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct "
               "(config.json; arXiv:2411.15242)",
    )
