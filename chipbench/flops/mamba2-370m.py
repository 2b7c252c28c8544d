"""Model FLOPs per token of a Mamba-2 training step, from shapes alone.

Counted: every matmul and einsum of the forward pass at 2 FLOPs per
multiply-add, as the chunked SSD algorithm (arXiv:2405.21060, chunk Q)
computes them, whole Q x Q blocks included; the backward pass as twice the
forward.  Not counted: elementwise work (norms, gates, softplus, exp), and
the forward that activation checkpointing computes again.
"""


def per_token(m: dict) -> float:
    D, L, V = m["d_model"], m["n_layers"], m["vocab_size"]
    Di = m["ssm_expand"] * D
    P, N, Q, W = m["ssm_head_dim"], m["ssm_state"], m["ssm_chunk"], \
        m["conv_width"]
    H = Di // P
    F = 2 * Di + 2 * N + H          # in-projection width: z, x, B, C, dt
    C = Di + 2 * N                  # conv channels: x, B, C
    layer = (2 * D * F              # in-projection
             + 2 * W * C            # depthwise causal conv
             + 2 * Q * N            # C.B scores inside a chunk
             + 2 * Q * H * P        # scores x inputs inside a chunk
             + 2 * H * P * N        # chunk states
             + 2 * H * P * N        # states read out by C
             + 2 * H * P * N / Q    # state passing between chunks
             + 2 * Di * D)          # out-projection
    forward = L * layer + 2 * D * V  # + the tied output head
    return 3.0 * forward
