"""Model FLOPs of a Nemotron-H chain node's train step, from shapes alone.

Counted: every matmul and einsum of the forward pass at 2 FLOPs per
multiply-add; the backward pass as twice the forward.  Mamba-2 layers as the
chunked SSD algorithm (arXiv:2405.21060, chunk Q) computes them, whole Q x Q
blocks included, with C.B once per group; attention over the causal half
(query t against keys 1..t, on average (S + 1) / 2 of them); MoE layers as
the router over all experts, the held experts at their expected load of
``top_k * held / n_experts`` rows a token (uniform routing; the step's own
count is the ``moe_rows`` counter), and the shared expert.  Not counted:
elementwise work (norms, gates, softplus, exp, routing), the forward that
activation checkpointing computes again, and the masked upper half of the
attention scores that the blocked attention computes.
"""

SEQ = 4096    # the sequence of the traffic this configuration is run with


def per_token(m: dict, seq: int = SEQ) -> float:
    D, V = m["d_model"], m["vocab_size"]
    P, H, G, N, Q, W = (m["ssm_head_dim"], m["ssm_heads"], m["ssm_groups"],
                        m["ssm_state"], m["ssm_chunk"], m["conv_width"])
    Di = H * P
    F = 2 * Di + 2 * G * N + H      # in-projection width: z, x, B, C, dt
    C = Di + 2 * G * N              # conv channels: x, B, C
    mamba = (2 * D * F              # in-projection
             + 2 * W * C            # depthwise causal conv
             + 2 * Q * N * G        # C.B scores inside a chunk, per group
             + 2 * Q * H * P        # scores x inputs inside a chunk
             + 2 * H * P * N        # chunk states
             + 2 * H * P * N        # states read out by C
             + 2 * H * P * N / Q    # state passing between chunks
             + 2 * Di * D)          # out-projection
    E, Eh, k = m["n_experts"], m["moe_experts_held"], m["moe_top_k"]
    moe = (2 * D * E                                  # router
           + k * Eh / E * 2 * 2 * D * m["moe_d_ff"]   # held experts: up, down
           + 2 * 2 * D * m["moe_shared_d_ff"])        # shared expert
    Hq, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn = (2 * D * (Hq + 2 * Hkv) * hd          # q, k, v projections
            + 2 * 2 * (seq + 1) / 2 * Hq * hd    # scores and values, causal
            + 2 * Hq * hd * D)                   # out-projection
    per_kind = {"ssd": mamba, "moe_only": moe, "attn_only": attn}
    reps = m["n_layers"] // len(m["pattern"])
    forward = reps * sum(per_kind[k] for k in m["pattern"]) + 2 * D * V
    return 3.0 * forward


def gmm_flops(m: dict, rows: float) -> float:
    """FLOPs of the held experts' grouped matmuls over ``rows`` routed rows:
    two matrices (up, down), each in four products as the step runs them
    (the forward, its recompute, and the backward's two: the input's and
    the weights' cotangents)."""
    return 2 * rows * m["d_model"] * m["moe_d_ff"] * 2 * 4
