"""BENCHMARK.json and the files it names: present, consistent, and equal to
the published configurations but for the keys they list as changed."""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_keys_names_and_files():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["why"]) <= 200
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        for c in m.get("workloads", []):
            assert c in cells
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        moved = e2e[m["moves"]]
        for c in m["workloads"]:
            assert c in moved.get("workloads", [c])
    for cell in cells:
        reported = [m for m in SPEC["end_to_end"]
                    if cell in m.get("workloads", [cell])]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])


# The published mamba2-370m: its config.json (n_layer, d_model, vocab_size
# 50277 padded to a multiple of pad_vocab_size_multiple 16, rms_norm,
# residual_in_fp32, tie_embeddings, d_intermediate 0) and the defaults of
# mamba_ssm's Mamba2 layer it names (d_state, d_conv, expand, headdim,
# ngroups, chunk_size, conv_bias, the RMSNorm eps), under the keys of the
# configuration file.
PUBLISHED_MAMBA2_370M = {
    "n_layers": 48, "d_model": 1024, "vocab_size": 50288,
    "ssm_state": 128, "ssm_head_dim": 64, "ssm_expand": 2,
    "ssm_chunk": 256, "conv_width": 4, "norm_eps": 1e-5,
    "tie_embeddings": True, "d_ff": 0, "conv_bias": True,
    "residual_in_fp32": True,
}
PUBLISHED_IDS = 50277


@pytest.mark.parametrize("name", ["mamba2-370m", "mamba2-370m-decay"])
def test_mamba2_370m_files_are_the_published_model(name):
    from chipbench.drivers.train import model_config

    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    mc = model_config(cfg)
    stated = {k: (cfg[k] if k in cfg else getattr(mc, k))
              for k in PUBLISHED_MAMBA2_370M}
    changed = {k for k, v in PUBLISHED_MAMBA2_370M.items() if stated[k] != v}
    assert changed == set(cfg["reduced"]) == {"conv_bias", "residual_in_fp32"}
    listed = {c["name"]: c for c in SPEC["configs"]}
    if name in listed:
        assert set(listed[name]["reduced"]) == changed
    traffic = json.loads((BENCH / "traffic" / "train-b8-s2048.json")
                         .read_text())
    assert traffic["vocab_used"] == PUBLISHED_IDS


def test_configurations_differ_only_in_weight_decay():
    a, b = (json.loads((BENCH / "configs" / f"{n}.json").read_text())
            for n in ("mamba2-370m", "mamba2-370m-decay"))
    for d in (a, b):
        d.pop("name"), d.pop("assumed")
    assert a["optimizer"].pop("weight_decay") == 0.0
    assert b["optimizer"].pop("weight_decay") == 0.1
    assert a == b


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_is_found_by_name(cell):
    from chipbench import harness

    found = harness.load_cell(cell)
    assert harness.driver_class(found["traffic"]) is not None
    correct = found["config"]["correct"]
    assert f"grad_norm_gap_{correct['grad_leaf']}" in correct["limits"]
    for m in found["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
