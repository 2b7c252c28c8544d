"""Cost-model profiles: Table I fidelity + analytic arch profiles sanity."""
import pytest

from repro.configs import ARCHS
from repro.core import FW, BW, resnet101_profile
from repro.models.profiles import active_params, model_profile, total_params

EXPECTED_PARAMS_B = {  # nameplate sanity bands
    "qwen3-moe-30b-a3b": (28, 33),
    "arctic-480b": (450, 500),
    "llama-3.2-vision-90b": (80, 95),
    "qwen2-1.5b": (1.3, 1.8),
    "starcoder2-7b": (6.5, 8.0),
    "gemma2-27b": (25, 29),
    "qwen3-14b": (13, 16),
    "recurrentgemma-9b": (8.3, 10.5),
    "whisper-small": (0.15, 0.4),
    "mamba2-370m": (0.3, 0.45),
    "nemotron3-nano-30b-a3b": (31, 32.2),   # published 31.6B
}


def test_resnet101_table1():
    prof = resnet101_profile()
    assert prof.L == 37
    # spot values straight from Table I
    assert prof.layers[0].flops_fw == pytest.approx(236.02e6)
    assert prof.layers[2].mem_bytes == pytest.approx(3.02e6)
    assert prof.layers[32].mem_bytes == pytest.approx(234.92e6)
    assert prof.layers[35].act_bytes == 8192
    assert prof.layers[36].act_bytes == 4000
    # paper characteristics: (C1) middle layers dominate compute
    mid = prof.seg_flops(3, 35, FW)
    assert mid / prof.total_flops(FW) > 0.95
    # (C2) smashed data size non-increasing after layer 2
    acts = [l.act_bytes for l in prof.layers]
    assert all(a >= b for a, b in zip(acts[2:], acts[3:]))
    # BW = 2x FW (paper rounds to 3 significant digits, e.g. 12.9 vs 2x6.43)
    for l in prof.layers:
        assert l.flops_bw == pytest.approx(2 * l.flops_fw, rel=5e-3)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_arch_profile_sane(arch):
    cfg = ARCHS[arch]
    lo, hi = EXPECTED_PARAMS_B[arch]
    n = total_params(cfg) / 1e9
    assert lo <= n <= hi, f"{arch}: {n}B params out of band ({lo},{hi})"
    assert active_params(cfg) <= total_params(cfg)
    prof = model_profile(cfg, seq_len=4096, mode="train")
    assert prof.L == 1 + cfg.enc_layers + cfg.n_layers + 1
    for l in prof.layers:
        assert l.flops_fw >= 0 and l.mem_bytes >= 0
        assert l.flops_bw == pytest.approx(2 * l.flops_fw)
    # decode flops per token << train flops per sequence (excluding encoder
    # rows: the chain profile charges the enc once per request, not per token)
    dec = model_profile(cfg, seq_len=4096, mode="decode", cache_len=32768)
    dec_flops = sum(l.flops_fw for l in dec.layers[1 + cfg.enc_layers:])
    assert dec_flops < prof.total_flops(FW) / 100


@pytest.mark.parametrize("arch", ["qwen3-14b", "qwen3-moe-30b-a3b", "mamba2-370m"])
def test_planner_runs_on_arch_profiles(arch):
    """The paper's planner consumes every arch profile (DESIGN.md Sec. 3)."""
    from repro.core import IF, TR, ServiceChainRequest, bcd_solve, exact_solve, tpu_pod_topology

    cfg = ARCHS[arch]
    prof = model_profile(cfg, seq_len=4096, mode="train")
    net = tpu_pod_topology(n_groups=8, chips_per_group=32)
    nodes = sorted(net.nodes)
    K = 4
    cands = [[nodes[0]]] + [nodes[1:4], nodes[4:7]] + [[nodes[-1]]]
    req = ServiceChainRequest(cfg.name, nodes[0], nodes[-1], 8, TR)
    opt = exact_solve(net, prof, req, K, cands)
    heur = bcd_solve(net, prof, req, K, cands)
    assert opt.feasible and heur.feasible
    assert heur.latency_s <= 1.5 * opt.latency_s + 1e-9


def test_expert_share_and_single_mixer_blocks_are_priced():
    """A chain node of Nemotron-H (one period E M E M E M *, 8 of 128
    experts held, a 16,384-id vocabulary slice) prices its attention-only
    and MoE-only blocks and its held experts: 528.1 M parameters, the held
    experts at their expected load, and a plan of its one group on K = 1."""
    import dataclasses

    from repro.configs.nemotron3_nano_30b_a3b import PERIOD
    from repro.msl import plan_pipeline

    full = ARCHS["nemotron3-nano-30b-a3b"]
    cfg = dataclasses.replace(full, n_layers=len(PERIOD), pattern=PERIOD,
                              vocab_size=16384, moe_experts_held=8)
    assert total_params(cfg) == 528_111_936
    prof = model_profile(cfg, seq_len=4096, mode="train")
    rows = {l.name: l for l in prof.layers}
    D, S = 2688, 4096
    held = 6 * 8 / 128 * 2 * 2 * S * D * 1856
    moe = 2 * S * D * 128 + held + 2 * 2 * S * D * 3712
    assert rows["moe_only0"].flops_fw == pytest.approx(moe)
    attn = 2 * S * D * (32 + 4) * 128 + 2 * S * 4096 * D \
        + 2 * 2 * S * S / 2 * 32 * 128
    assert rows["attn_only6"].flops_fw == pytest.approx(attn)
    # the uncut layer holds every expert and runs top-6 of them a token
    whole = dataclasses.replace(cfg, moe_experts_held=0)
    per_expert = 2 * D * 1856
    assert total_params(whole) - total_params(cfg) == 3 * 120 * per_expert
    assert active_params(whole) < total_params(whole)
    plan = plan_pipeline(cfg, seq_len=S, microbatch=2, candidate_K=(1,))
    assert plan.K == 1 and plan.segments == [(1, 1)] and plan.n_groups == 1
    assert plan.predicted_latency_s > 0
