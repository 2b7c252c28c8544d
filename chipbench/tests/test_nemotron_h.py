"""The Nemotron-H chain node against its plain reference on the CPU, at a
small size: one period (E M E M E M *), d_model 64, 8 Mamba heads of 16 in
2 SSM groups, 4 of 16 experts held with top-6 sigmoid routing, GQA 4/2
heads of 16, vocab 256.  The system runs its pipeline train step (K = 1,
M = 2); the reference is ``chipbench/models/nemotron_h.py``.  Also whole
runs of the cell's driver at that size, with and without a planted fault."""
import contextlib
import copy
import importlib.util
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import faults, harness
from chipbench.models import nemotron_h as ref

BENCH = Path(__file__).resolve().parents[1]
CELL = "train-nemotron3-nano-ep16-s4096-k1"
CONFIG = json.loads((BENCH / "configs" / "nemotron3-nano-30b-a3b-ep16.json")
                    .read_text())
TINY = dict(d_model=64, vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
            ssm_state=16, ssm_head_dim=16, ssm_heads=8, ssm_groups=2,
            ssm_chunk=16, n_experts=16, moe_experts_held=4, moe_d_ff=32,
            moe_shared_d_ff=48, loss_chunk=128, q_chunk=16)
B, S, M = 4, 64, 2


def _tiny(compute_dtype="bfloat16"):
    cfg = copy.deepcopy(CONFIG)
    cfg["model"].update(TINY, compute_dtype=compute_dtype)
    return cfg


def _system_step(cfg, params, batch):
    """One pipeline train step from ``params``: the gradient (AdamW's first
    moment after step 1, unbiased), the parameters after it and the
    metrics."""
    from chipbench.drivers.train import model_config
    from repro.msl import plan_on_devices
    from repro.msl.pipeline import make_pipeline_train_step
    from repro.optim import adamw, cosine_schedule

    o = cfg["optimizer"]
    mcfg = model_config(cfg)
    plan, mesh = plan_on_devices(mcfg, 1, seq_len=S, microbatch=B // M)
    opt = adamw(cosine_schedule(o["lr"], o["warmup"], o["total"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    step = jax.jit(make_pipeline_train_step(mcfg, mesh, plan, M, opt))
    p1, state, metrics = step(params, opt.init(params), batch)
    grads = jax.tree.map(lambda m: m / (1 - o["b1"]), state["m"])
    return grads, p1, metrics


def _reference_step(cfg, params, batch):
    """The reference's loss, gradient and first AdamW update (at step 1,
    ``m / sqrt(v)`` is ``g / |g|``), and its count of (token, slot) pairs
    routed to held experts."""
    m, o = cfg["model"], cfg["optimizer"]
    loss, grads = jax.value_and_grad(ref._nll_sum(m, False))(
        params, batch["tokens"], batch["targets"])
    n = B * S
    grads = jax.tree.map(lambda g: g / n, grads)
    lr1 = o["lr"] / o["warmup"]
    p1 = jax.tree.map(lambda p, g: p - lr1 * g / (jnp.abs(g) + o["eps"]),
                      params, grads)
    return float(loss) / n, grads, p1, _held_pairs(m, params, batch)


def _held_pairs(m, params, batch):
    """(token, slot) pairs the reference routes to held experts, over the
    MoE layers, from the same residual stream the reference computes."""
    d = ref.dims(m)
    q = ref._rounder(False)
    x = params["embed"][batch["tokens"]]
    pairs = 0
    for kind, g in zip(m["pattern"], params["stack"]["groups"]):
        p = jax.tree.map(lambda a: a[0], g)
        if kind == "moe_only":
            h = ref._rmsnorm(x, p["ln1"], m["norm_eps"])
            idx, _ = ref.route(m, d, h, p["moe"])
            pairs += int(jnp.sum(idx < d["Eh"]))
        x = x + ref.MIXERS[kind](m, d, q, x, p)
    return pairs


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# float32 (the system's compute dtype set to float32): both sides compute
# the same equations in float32 (CPU dots are exact float32) and differ in
# the order of sums (the chunked scans, the blocked attention, the grouped
# matmul), a relative 1e-7 an operation.  Observed over three seeds: loss
# 1.6e-7, gradient per leaf 5.7e-6, update per leaf 6.9e-4 (an element
# whose gradient is near 0 can move the other way, by 2 lr); limits 1e-5,
# 1e-4 and 5e-3.  Both route the same, so the counter equals the pairs.
F32_TOL = {"loss": 1e-5, "grad": 1e-4, "update": 5e-3}
# bfloat16 (the cell's dtype): every activation and dot operand rounds to 8
# bits through seven layers of width 64, so a leaf's gradient moves by a few
# percent, and a near-tie of the router picks another expert.  Observed over
# three seeds: loss 2.1e-4, wo's gradient 0.031, the median leaf's 0.064,
# rows 2 of 1,200 away from the pairs; limits 2e-3, 0.1, 0.15 and 1%.
BF16_TOL = {"loss": 2e-3, "wo": 0.1, "median": 0.15, "rows": 0.01}


def _leaves(params, *trees):
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    return zip(names, *(jax.tree.leaves(t) for t in (params,) + trees))


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_reference_matches_the_system(compute_dtype):
    """The loss, the gradient of every leaf and the first AdamW update of
    the system's pipeline train step against the reference's, and the MoE
    counter against the pairs the reference routes to held experts."""
    cfg = _tiny(compute_dtype)
    params = ref.init_params(cfg["model"], 2**33 + 5)
    batch = ref.make_batches(cfg["model"], 2**33 + 5, 1, B, S, 256)[0]
    grads, p1, metrics = _system_step(cfg, params, batch)
    loss, r_grads, r_p1, pairs = _reference_step(cfg, params, batch)
    gaps = {}
    for name, p0, g, rg, u, ru in _leaves(params, grads, r_grads, p1, r_p1):
        if "router_bias" in name:      # selection only: no gradient
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(rg))
            np.testing.assert_array_equal(np.asarray(u), np.asarray(p0))
            continue
        gaps[name] = (_rel(g, rg), _rel(u - p0, ru - p0))
    loss_gap = abs(float(metrics["loss"]) - loss) / abs(loss)
    rows = int(metrics["moe_rows"])
    if compute_dtype == "float32":
        tol = F32_TOL
        assert loss_gap <= tol["loss"]
        for name, (g, u) in gaps.items():
            assert g <= tol["grad"] and u <= tol["update"], (name, g, u)
        # dropless: every pair routed to a held expert is a computed row
        assert rows == pairs > 0
    else:
        tol = BF16_TOL
        assert loss_gap <= tol["loss"]
        [wo] = [g for name, (g, _) in gaps.items() if name.endswith("['wo']")]
        assert wo <= tol["wo"]
        assert sorted(g for g, _ in gaps.values())[len(gaps) // 2] \
            <= tol["median"]
        assert abs(rows - pairs) <= tol["rows"] * pairs


def _run(fault=None, seconds=0.5):
    found = copy.deepcopy(harness.load_cell(CELL))
    found["config"]["model"].update(TINY)
    found["traffic"].update(batch=B, seq=S, n_micro=M, vocab_used=256)
    args = SimpleNamespace(workload=CELL, seed=2**31 + 21, seconds=seconds,
                           trace=0)
    harness.driver_class(found["traffic"])    # registers its faults
    plant = (faults.FAULTS[found["traffic"]["driver"]][fault] if fault
             else contextlib.nullcontext)
    with plant():
        return harness.run_cell(args, time.perf_counter(), None, found)


@pytest.mark.parametrize("fault", [None, "unchanged", "half"])
def test_cell_faults_are_caught(fault):
    res = _run(fault)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"


def test_cell_control_is_caught():
    """The control (the reference with float8 where the system holds
    bfloat16) in the program's place fails the cell's limits."""
    found = copy.deepcopy(harness.load_cell(CELL))
    found["config"]["model"].update(TINY)
    found["traffic"].update(batch=B, seq=S, n_micro=M, vocab_used=256)
    cell = harness.driver_class(found["traffic"])(
        found["config"], found["traffic"], 2**31 + 22, 0.5)
    rec = cell.run_window(lambda name: contextlib.nullcontext())
    assert rec["moe_rows"] > 0 and rec["moe_max_rows"] > 0
    nums, _ = cell.check(rec, control=True)
    assert any(v > cell.limits[k] for k, v in nums.items()), nums


def _flops():
    spec = importlib.util.spec_from_file_location(
        "flops_nemotron", BENCH / "flops" / "nemotron3-nano-30b-a3b-ep16.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_per_token_count_matches_the_hand_count():
    # per token, forward (D 2688, S 4096):
    #   Mamba-2 (H 64, P 64, G 8, N 128, Q 128; in-proj 10,304 wide, conv
    #   6,144 channels): 55,394,304 + 49,152 + 262,144 + 1,048,576
    #   + 2 * 1,048,576 + 8,192 + 22,020,096 = 80,879,616
    #   MoE: router 688,128 + experts 6 * 8 / 128 * 4 * 2688 * 1856
    #   = 7,483,392 + shared 39,911,424 = 48,082,944
    #   attention: q, k, v 24,772,608 + scores and values
    #   2 * 4097 * 32 * 128 = 33,562,624 + out 22,020,096 = 80,355,328
    #   head 2 * 2688 * 16384 = 88,080,384
    # 3 * (80,879,616 + 48,082,944) + 80,355,328 + 88,080,384 = 555,323,392;
    # x 3 (forward and backward)
    assert _flops().per_token(CONFIG["model"]) == 1_665_970_176


def test_gmm_flops_count_both_matrices_in_four_products():
    assert _flops().gmm_flops(CONFIG["model"], 1) == 2 * 2688 * 1856 * 2 * 4
