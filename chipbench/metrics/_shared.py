"""Readings shared by the per-layer metric files of several cells."""
from __future__ import annotations


def idle_share(trace):
    """1 - device busy / traced window, %."""
    if trace is None or trace.window_s <= 0 or trace.n_devices == 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)


def flops_per_token(cfg: dict) -> float:
    """Training FLOPs per token of a configuration, from its own file
    ``chipbench/flops/<config name>.py``."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "flops" / f"{cfg['name']}.py"
    spec = importlib.util.spec_from_file_location("chipbench_flops", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.per_token(cfg["model"])


def peak(device_kind: str, key: str) -> float:
    """A published peak of one chip; an unknown device kind is an error."""
    import json
    from pathlib import Path

    table = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return float(table[device_kind][key])
