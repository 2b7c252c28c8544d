"""Pipeline step: model FLOPs per token (chipbench/flops/<config>.py) times
tokens per second, over the bfloat16 peak of the chips (chipbench/peaks.json),
in %."""
from chipbench.metrics._shared import flops_per_token, peak


def read(rec, trace):
    if rec.get("kind") != "train":
        return None
    rate = rec["tokens"] / rec["elapsed_s"]
    return 100.0 * flops_per_token(rec["config"]) * rate / (
        peak(rec["device_kind"], "bf16_flops_per_s") * rec["chips"])
