"""MSL-PP benchmark (§Perf hillclimb #3): the paper's planner driving pipeline
parallelism on the production mesh, vs the dp-tp baseline.

For each featured arch it (1) runs the BCD planner on the pod-level topology to
pick K and the per-stage group segments, (2) lowers + compiles the pipelined
train step on a ('stage','data') mesh carved from the 512 fake devices, and
(3) reports the roofline terms next to the dp-tp dry-run cell.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import Row

ART = Path(__file__).resolve().parents[1] / "artifacts"
SRC = str(Path(__file__).resolve().parents[1] / "src")

FEATURED = ["qwen3-14b", "gemma2-27b"]


def _run_pp_cell(arch: str, timeout: float = 2400.0) -> dict:
    out = ART / "msl_pp" / f"{arch}__train_4k.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    if out.exists():
        return json.loads(out.read_text())
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never the chip
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun_pp", arch, str(out)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        return {"status": "error", "stderr": proc.stderr[-2000:]}
    return json.loads(out.read_text())


def run(quick: bool = False) -> list[Row]:
    rows: list[Row] = []
    dp = {}
    for f in (ART / "dryrun").glob("*train_4k__multi.json"):
        j = json.loads(f.read_text())
        dp[j["arch"]] = j
    for arch in (FEATURED[:1] if quick else FEATURED):
        j = _run_pp_cell(arch)
        name = f"msl_pp_{arch}_train_4k"
        if j.get("status") != "ok":
            rows.append(Row(name, float("nan"),
                            f"error:{j.get('stderr', '')[:120]}"))
            continue
        r = j["roofline"]
        t_dom = max(r["t_compute"], r["t_memory"], r["t_collective"])
        derived = (
            f"plan_K={j['plan']['K']};segments={j['plan']['segments']};"
            f"predicted_ms={j['plan']['predicted_latency_s']*1e3:.1f};"
            f"tc={r['t_compute']:.3f}s;tm={r['t_memory']:.3f}s;"
            f"tx={r['t_collective']:.3f}s;mem={j['memory']['per_device_bytes']/2**30:.1f}GB"
        ).replace(",", ";")
        d = dp.get(arch)
        if d and d.get("status") == "ok":
            dt = max(d["roofline"]["t_compute"], d["roofline"]["t_memory"],
                     d["roofline"]["t_collective"])
            derived += f";dp_tp_tdom={dt:.3f}s;mem_dp={d['memory']['per_device_bytes']/2**30:.1f}GB"
        rows.append(Row(name, t_dom * 1e6, derived))
    return rows
