"""Trip-count-aware HLO cost analysis: closed-form toys (the A0 meta-iteration
of EXPERIMENTS.md §Perf) + collective parsing."""
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # subprocess dry-runs compile whole models

SRC = str(Path(__file__).resolve().parents[1] / "src")

TOY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.roofline.hlo_cost import analyze_hlo

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))

def step(w, x):
    def body(h, wi):
        return jnp.tanh(h @ wi), None
    h, _ = jax.lax.scan(body, x, w)
    return h.sum()

ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32, sharding=NamedSharding(mesh, P()))
xs = jax.ShapeDtypeStruct((8, 64), jnp.float32, sharding=NamedSharding(mesh, P()))
mc = analyze_hlo(jax.jit(step).lower(ws, xs).compile().as_text(), 4)
expected = 7 * 2 * 8 * 64 * 64
assert mc.unknown_trip_counts == 0, mc.unknown_trip_counts
assert expected <= mc.flops <= expected * 1.05, (mc.flops, expected)

# sharded variant: per-device flops + per-iteration all-gather bytes (the
# constraint inside the loop keeps the weight gather un-hoistable)
ws2 = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32, sharding=NamedSharding(mesh, P(None, "data")))
def step2(w, x):
    def body(h, wi):
        h = jax.lax.with_sharding_constraint(h, NamedSharding(mesh, P("data")))
        return jnp.tanh(h @ wi), None
    h, _ = jax.lax.scan(body, x, w)
    return h.sum()
mc2 = analyze_hlo(jax.jit(step2).lower(ws2, xs).compile().as_text(), 4)
assert expected / 4 * 0.9 <= mc2.flops <= expected * 1.3, mc2.flops
# collectives inside the loop body must be multiplied by the trip count
total_coll = mc2.total_coll_bytes
per_iter = 0.75 * 64 * 64 * 4  # ring (n-1)/n x one weight slice
assert total_coll >= 5 * per_iter, (total_coll, per_iter)
print("TOY OK")
"""


def test_hlo_cost_toys():
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", TOY], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "TOY OK" in proc.stdout


def test_collective_volume_formulas():
    from repro.roofline.analysis import collective_stats

    hlo = """
ENTRY %main () -> f32[] {
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={{0,1,2,3}}
  %ag = f32[4096]{0} all-gather(%y), replica_groups=[1,4]<=[4], dimensions={0}
  %rs = f32[256]{0} reduce-scatter(%z), replica_groups={{0,1,2,3}}
  %cp = f32[512]{0} collective-permute(%w), source_target_pairs={{0,1}}
}
"""
    st = collective_stats(hlo, 4)
    assert st["counts"] == {"all-reduce": 1, "all-gather": 1,
                            "reduce-scatter": 1, "all-to-all": 0,
                            "collective-permute": 1}
    assert st["bytes_per_device"]["all-reduce"] == pytest.approx(
        2 * 0.75 * 1024 * 4)
    assert st["bytes_per_device"]["all-gather"] == pytest.approx(
        0.75 * 4096 * 4)
    assert st["bytes_per_device"]["reduce-scatter"] == pytest.approx(
        0.75 * 256 * 4 * 4)
    assert st["bytes_per_device"]["collective-permute"] == pytest.approx(512 * 4)


def test_roofline_terms_and_bottleneck():
    from repro.roofline.analysis import Roofline

    r = Roofline(arch="a", shape="s", mesh="m", chips=256,
                 flops_per_device=197e12,  # exactly 1 s of compute
                 hbm_bytes_per_device=819e9 * 2,  # 2 s of memory
                 coll_bytes_per_device=50e9 * 0.5,  # 0.5 s of collectives
                 model_flops_global=197e12 * 256)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    # at the memory bound, achievable useful throughput is half of peak
    assert r.roofline_fraction == pytest.approx(0.5)
    assert r.useful_flops_ratio == pytest.approx(1.0)


def test_dryrun_single_cell_end_to_end(tmp_path):
    """Smallest real cell compiles + produces a sound artifact (slow-ish)."""
    import json
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--cell", "mamba2-370m",
         "long_500k", "single"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(Path(SRC).parent))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    art = Path(SRC).parent / "artifacts" / "dryrun" / \
        "mamba2-370m__long_500k__single.json"
    j = json.loads(art.read_text())
    assert j["status"] == "ok"
    assert j["memory"]["fits_16gb"]
    assert j["roofline"]["bottleneck"] in ("compute", "memory", "collective")
    assert j["cost"]["flops_per_device"] > 0
