"""MoE experts (the megablox grouped-matmul kernels of
``models/layers._grouped_matmul``): their share of the bfloat16 MXU peak, %.

The work is counted from the step's ``moe_rows`` counter, the rows the held
experts computed (``chipbench/flops/<config>.py``, ``gmm_flops``); the time
is the device time of the step's Mosaic kernels (custom calls with target
``tpu_custom_call``) that the compiled text bills to the ``moe`` scope, where
the grouped matmuls are the only kernels.  None where the run has no MoE
counter (a program without them) or ``read_scopes`` gives none.  Finding the
kernels takes the compiled text once more (``scopes.compiled_step_text``),
after the window.
"""
import importlib.util
import re
from pathlib import Path

from chipbench import harness, scopes
from chipbench.metrics._shared import peak

_KERNEL = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .* custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"')


def gmm_flops(cfg: dict, rows: float) -> float:
    path = Path(__file__).resolve().parents[1] / "flops" / f"{cfg['name']}.py"
    spec = importlib.util.spec_from_file_location("chipbench_flops_gmm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.gmm_flops(cfg["model"], rows)


def read(rec, trace):
    if not rec.get("moe_rows"):
        return None
    got = scopes.read_scopes(rec, trace)
    if got is None or got.ms_per_step(("moe",)) is None:
        return None
    found = harness.load_cell(scopes.running_cell())
    try:
        text = scopes.compiled_step_text(found["config"], found["traffic"])
    except ValueError:
        return None
    where = scopes.instruction_scopes(text)
    names = {m.group(1) for line in text.splitlines()
             if (m := _KERNEL.match(line))}
    kernel_s = sum(s for key, s in trace.ops.items()
                   if (name := scopes.instruction(key)) in names
                   and where[name][0] == "moe")
    if kernel_s <= 0:
        return None
    flops = gmm_flops(rec["config"], rec["moe_rows"])
    return 100.0 * flops / (kernel_s * peak(rec["device_kind"],
                                            "bf16_flops_per_s") * rec["chips"])
