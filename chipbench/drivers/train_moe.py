"""The train driver (``drivers/train.py``) for a model with MoE layers: it
also keeps the step's MoE counters of the measured window.

The pipeline train step returns, beside the loss, ``moe_rows`` (the rows the
held experts computed in the step, over every MoE layer and microbatch) and
``moe_max_rows`` (the largest held expert's rows in one layer and
microbatch).  They stay on the device while the window runs, so they add no
host round trip, and are copied to the host after it and added up into the
record: ``moe_rows`` (all of the window's steps) and ``moe_max_rows`` (the
largest of them).
"""
from __future__ import annotations

import jax

from chipbench import faults
from chipbench.drivers.train import Train

# the train step is the train driver's, and so are the faults planted in it
# (``chipbench/faults.py`` keys them by driver)
faults.FAULTS.setdefault("train_moe", faults.FAULTS["train"])


class TrainMoE(Train):

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        step, self.counts = self.step, []

        def counted(params, opt_state, batch):
            params, opt_state, m = step(params, opt_state, batch)
            self.counts.append((m["moe_rows"], m["moe_max_rows"]))
            return params, opt_state, m

        self.step = counted

    def run_window(self, annotate) -> dict:
        self.counts = []
        rec = super().run_window(annotate)
        # copies to the host only: no program runs on the device here
        counts = jax.device_get(self.counts)
        rec["moe_rows"] = sum(int(rows) for rows, _ in counts)
        rec["moe_max_rows"] = max(int(most) for _, most in counts)
        return rec

    def notes(self, rec: dict) -> dict:
        return {**super().notes(rec),
                "moe_rows_per_step": rec["moe_rows"] / rec["steps"],
                "moe_max_rows": rec["moe_max_rows"]}


CELL = TrainMoE
