"""chip_smoke.py's one-chip phases, run in-process on CPU at reduced size.

The test does the steering (a reduced model, the CPU backend); the script
itself refuses to run anywhere but a TPU, which the last test checks.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

pytest.importorskip("jax")

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_minplus_phase(chip_smoke):
    assert chip_smoke.minplus_check()["dtype"] == "float32"


def test_admission_phase_runs_batched_with_plan_parity(chip_smoke):
    from repro.core.engine import SOLVE_BATCH_MIN_BATCH

    res = chip_smoke.admission(n_requests=32)
    assert res["requests"] == 32
    assert 0 < res["accepted"] <= 32
    assert res["batched_ticks"] >= 1
    assert res["instances_solved_batched"] >= SOLVE_BATCH_MIN_BATCH


def test_one_chip_chain_phase_reduced(chip_smoke):
    from repro.configs import get_config

    cfg = get_config(chip_smoke.ARCH).reduced()
    res = chip_smoke.chain(cfg, 1, seq=16, batch=4)
    R = cfg.n_layers // len(cfg.pattern)
    assert res["segments"] == [(1, R)]
    assert res["n_micro"] == 2
    assert res["max_err"] < 5e-2
    assert len(res["losses"]) == chip_smoke.STEPS
    assert res["param_delta"] > 0.0


def test_main_refuses_without_tpu(chip_smoke, capsys):
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "no TPU found" in out.err
    assert '"ok"' not in out.out
