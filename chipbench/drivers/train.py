"""Back-to-back donated train steps of a chain run as a pipeline.

Set-up plans the chain (``repro.msl.plan_on_devices``), builds the program's
donated pipeline train step (``repro.msl.pipeline.make_pipeline_train_step``)
with its AdamW state, makes the weights and the batches on the device from the
seed, and drives that one step object through its first ``CHECK_STEPS`` steps
on distinct batches: this compiles it, and the losses, the first gradient (as
AdamW's first moment holds it after step 1) and the parameters' change after
the last of them are what ``check`` compares with the plain reference.  The
window then continues the same object: steps run back to back, each donated,
one in flight, and the last one ends in ``block_until_ready``.
"""
from __future__ import annotations

import dataclasses
import importlib
import time

CHECK_STEPS = 3
N_BATCHES = 8          # distinct batches, cycled by the window


def model_config(cfg: dict):
    """The program's configuration of the model, as the file states it."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(cfg["arch"]), **cfg["model"])


class Train:
    """A training cell: one configuration, one batch and sequence shape."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float):
        import jax
        import jax.numpy as jnp

        from repro.msl import plan_on_devices
        from repro.msl.pipeline import make_pipeline_train_step
        from repro.optim import adamw, cosine_schedule

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.seconds = float(seconds)
        # the compared numbers' limits and the steady leaf, per configuration
        self.limits = cfg["correct"]["limits"]
        self.grad_leaf = cfg["correct"]["grad_leaf"]
        self.model = importlib.import_module(
            f"chipbench.models.{traffic['model']}")
        self.model.check_config(cfg)
        self.mcfg = mcfg = model_config(cfg)
        B, S, M = traffic["batch"], traffic["seq"], traffic["n_micro"]
        self.tokens_per_step = B * S
        plan, mesh = plan_on_devices(mcfg, traffic["stages"], seq_len=S,
                                     microbatch=B // M)
        self.plan = plan
        o = cfg["optimizer"]
        assert o["name"] == "adamw", o["name"]
        opt = adamw(cosine_schedule(o["lr"], o["warmup"], o["total"]),
                    b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"])
        self.step = jax.jit(make_pipeline_train_step(mcfg, mesh, plan, M,
                                                     opt),
                            donate_argnums=(0, 1))
        self.batches = self.model.make_batches(
            cfg["model"], seed, N_BATCHES, B, S, traffic["vocab_used"])
        params = self.model.init_params(cfg["model"], seed)
        self.leaves = [jax.tree_util.keystr(path) for path, _ in
                       jax.tree_util.tree_flatten_with_path(params)[0]]
        p0 = jax.tree.map(jnp.copy, params)
        opt_state = jax.jit(opt.init)(params)
        b1 = o["b1"]
        losses = []
        for i in range(CHECK_STEPS):
            params, opt_state, m = self.step(params, opt_state,
                                             self.batches[i])
            losses.append(m["loss"])
            if i == 0:
                grad1 = self.model.leaf_norms(jax.tree.map(
                    lambda x: x / (1 - b1), opt_state["m"]))
        delta = self.model.leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        del p0
        self.got = {"losses": [float(x) for x in losses], "grad1": grad1,
                    "delta": delta}
        self.params, self.opt_state = params, opt_state
        self.n_done = CHECK_STEPS

    def run_window(self, annotate) -> dict:
        params, opt_state = self.params, self.opt_state
        self.params = self.opt_state = None
        n, prev = 0, None
        clock = time.perf_counter
        t0 = clock()
        while True:
            batch = self.batches[(self.n_done + n) % N_BATCHES]
            with annotate("train_step"):
                params, opt_state, m = self.step(params, opt_state, batch)
            n += 1
            if prev is not None:
                prev.block_until_ready()
            prev = m["loss"]
            if clock() - t0 >= self.seconds:
                break
        prev.block_until_ready()
        elapsed = clock() - t0
        self.last_loss = float(prev)
        del params, opt_state   # frees the device for the reference
        return {"kind": "train", "offered": n, "steps": n,
                "tokens": n * self.tokens_per_step, "elapsed_s": elapsed,
                "window_s": self.seconds, "config": self.cfg}

    def end_to_end(self, rec: dict) -> dict:
        return {"train_tokens_per_s": rec["tokens"] / rec["elapsed_s"]}

    def notes(self, rec: dict) -> dict:
        return {"steps": rec["steps"], "elapsed_s": rec["elapsed_s"],
                "step_s": rec["elapsed_s"] / rec["steps"],
                "segments": self.plan.segments,
                "check_losses": self.got["losses"],
                "last_loss": self.last_loss}

    def check(self, rec: dict, control: bool = False,
              decayed=None) -> tuple[dict, dict]:
        """The first steps against the reference from the same weights and
        batches; ``control`` puts the reference, with float8 where the
        system holds bfloat16, in the program's place; ``decayed`` makes
        the reference decay those leaves instead of the configuration's."""
        want = self.reference(low=False, decayed=decayed)
        got = self.reference(low=True) if control else self.got
        return compare(want, got, self.leaves, self.grad_leaf)

    def reference(self, low: bool, decayed=None) -> dict:
        m = self.model
        params = m.init_params(self.cfg["model"], self.seed)
        return m.reference_steps(self.cfg["model"], self.cfg["optimizer"],
                                 params, self.batches[:CHECK_STEPS], low=low,
                                 decayed=decayed)


def _median(xs: list) -> float:
    s = sorted(xs)
    return s[len(s) // 2]


def compare(want: dict, got: dict, leaves: list,
            grad_leaf: str) -> tuple[dict, dict]:
    """The compared numbers: the parameters' change per leaf after the last
    checked step, each gap taken against the larger of the leaf's reference
    norm and the median leaf's, worst leaf (leaves whose reference gradient
    is under a thousandth of the median leaf's move by round-off alone under
    AdamW and are left out); and the gap of the first gradient's norm of the
    configuration's steady leaf ``grad_leaf``.  The losses, the worst leaf's
    and the median leaf's gradient gaps are reported beside them."""
    g_med = _median(want["grad1"])
    kept = [i for i, w in enumerate(want["grad1"]) if w >= 1e-3 * g_med]
    d_med = _median([want["delta"][i] for i in kept])
    upd = max(abs(got["delta"][i] - want["delta"][i])
              / max(want["delta"][i], d_med) for i in kept)

    def grad_gap(i):
        return abs(got["grad1"][i] - want["grad1"][i]) / want["grad1"][i]

    [j] = [i for i, name in enumerate(leaves)
           if name.endswith(f"['{grad_leaf}']")]
    med = sorted(range(len(leaves)), key=lambda i: want["grad1"][i])[
        len(leaves) // 2]
    reported = {
        "loss_gap_rel": max(abs(g - w) / abs(w) for g, w in
                            zip(got["losses"], want["losses"])),
        "grad_norm_gap_worst_leaf": max(
            abs(g - w) / max(w, g_med)
            for g, w in zip(got["grad1"], want["grad1"])),
        "grad_norm_gap_median_leaf": grad_gap(med),
        "median_leaf": leaves[med]}
    nums = {"update_norm_gap": upd, f"grad_norm_gap_{grad_leaf}": grad_gap(j)}
    return (nums,
            {"reported": reported, "leaves": leaves,
             "losses": got["losses"], "reference_losses": want["losses"],
             "grad1": got["grad1"], "reference_grad1": want["grad1"],
             "delta": got["delta"], "reference_delta": want["delta"],
             "leaves_left_out": [leaves[i] for i in range(len(leaves))
                                 if i not in kept]})


CELL = Train
