"""nemotron3-nano-30b-a3b [hybrid]: 52 single-mixer blocks, d_model=2688,
Mamba-2 (64 heads of 64, 8 B/C groups, state 128, conv 4 with bias, chunk
128), MoE (128 experts, sigmoid top-6 with a selection-only correction bias,
normalised and scaled by 2.5, relu^2 experts of 1856, one shared relu^2 expert
of 3712) and attention alone (GQA 32/2 heads of 128, no position embedding);
vocab=131072, untied head.  [hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
layer equations of Nemotron-H, arXiv:2504.03624]
"""
from .base import ModelConfig

# hybrid_override_pattern: M = Mamba-2, E = MoE, * = attention
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KIND = {"M": "ssd", "E": "moe_only", "*": "attn_only"}


def kinds(pattern: str) -> tuple[str, ...]:
    return tuple(KIND[c] for c in pattern)


# one whole period of the pattern, in its published ratio of kinds: layers
# 7-13 (EMEMEM*), the unit of a chain cut in depth
PERIOD = kinds("EMEMEM*")


CONFIG = ModelConfig(
    name="nemotron3-nano-30b-a3b",
    family="hybrid",
    n_layers=len(PUBLISHED_PATTERN),
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=131_072,
    pattern=kinds(PUBLISHED_PATTERN),
    use_rope=False,
    mlp_variant="relu2",
    n_experts=128,
    moe_top_k=6,
    moe_d_ff=1856,
    moe_router="sigmoid",
    moe_routed_scale=2.5,
    moe_shared_d_ff=3712,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_heads=64,
    ssm_groups=8,
    ssm_chunk=128,
    conv_width=4,
    conv_bias=True,
    norm_eps=1e-5,
    sub_quadratic=False,
    optimizer="adamw",
)
