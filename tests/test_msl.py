"""MSL pipeline: planner on arch profiles + shard_map runtime equivalence
(the runtime check needs >1 device, so it runs via subprocess with
xla_force_host_platform_device_count)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import ARCHS
from repro.msl import group_profile, plan_pipeline

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.mark.parametrize("arch", ["qwen3-14b", "gemma2-27b", "mamba2-370m",
                                  "qwen3-moe-30b-a3b"])
def test_plan_pipeline(arch):
    cfg = ARCHS[arch]
    plan = plan_pipeline(cfg, seq_len=4096, microbatch=8,
                         candidate_K=(2, 4, 8))
    assert 2 <= plan.K <= 8
    assert sum(plan.groups_per_stage) == plan.n_groups
    assert plan.predicted_latency_s > 0
    # segments are a contiguous partition
    lo_expect = 1
    for lo, hi in plan.segments:
        assert lo == lo_expect and hi >= lo
        lo_expect = hi + 1
    assert plan.segments[-1][1] == plan.n_groups


def test_group_profile_conserves_totals():
    cfg = ARCHS["gemma2-27b"]
    from repro.core import FW
    from repro.models.profiles import model_profile

    gp = group_profile(cfg, 4096, "train")
    full = model_profile(cfg, 4096, "train")
    block_rows = full.layers[1:-1]
    assert sum(l.flops_fw for l in gp.layers) == pytest.approx(
        sum(l.flops_fw for l in block_rows))
    assert sum(l.mem_bytes for l in gp.layers) == pytest.approx(
        sum(l.mem_bytes for l in block_rows))


@pytest.mark.slow  # subprocess shard_map pipeline run (~1 min per arch)
@pytest.mark.parametrize("arch", ["qwen3-14b", "mamba2-370m",
                                  "nemotron3-nano-30b-a3b"])
def test_pipeline_runtime_equivalence(arch):
    """Pipelined forward == sequential forward; pipelined train step runs."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.msl.pipeline_check", arch],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PIPELINE CHECK OK" in proc.stdout
