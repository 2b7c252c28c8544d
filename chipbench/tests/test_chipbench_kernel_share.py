"""The SSD kernel share (``metrics/ssd_kernel_share.train.py``): Mosaic
kernels found in a compiled text, their share of the ``ssd_scan`` scope's
time, 0.0 on a step without them, None where the scope reading is None."""
import copy
from types import SimpleNamespace

import pytest

from chipbench import harness, scopes

CELL = "train-mamba2-370m-k1"
METRIC = "ssd_kernel_share.train"
SCAN = "jit(train_step)/jvp()/while/body/stage/while/body/closed_call/ssd_scan"
BWD = ("jit(train_step)/transpose(jvp())/while/body/stage/while/body/"
       "closed_call/ssd_scan")

TEXT = f"""\
HloModule jit_train_step, is_scheduled=true

ENTRY %main (a: f32[4,8]) -> f32[4,8] {{
  %a = f32[4,8]{{1,0}} parameter(0)
  %dot.3 = f32[4,4]{{1,0}} convolution(%a, %a), dim_labels=bf_io->bf, metadata={{op_name="{SCAN}/einsum"}}
  %ssd_scan_fwd.1 = (f32[4,8]{{1,0}}, f32[4,8]{{1,0}}) custom-call(%a, %dot.3), custom_call_target="tpu_custom_call", metadata={{op_name="{SCAN}/ssd_scan_fwd/pallas_call"}}
  %ssd_scan_bwd.2 = f32[4,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}/ssd_scan_bwd/pallas_call"}}
  %custom-call.6 = s32[4]{{0}} custom-call(%a), custom_call_target="AssumeGatherIndicesInBound", metadata={{op_name="{SCAN}/gather"}}
  %gte.4 = f32[4,8]{{1,0}} get-tuple-element(%ssd_scan_fwd.1), index=0
  ROOT %fusion.5 = f32[4,8]{{1,0}} fusion(%gte.4), kind=kLoop, calls=%fused, metadata={{op_name="jit(train_step)/optimizer/mul"}}
}}
"""
OPS = {"%dot.3 = f32[4,4]": 0.375, "%custom-call.6 = s32[4]": 0.125,
       "%ssd_scan_fwd.1 = (f32[4,8]": 1.0, "%ssd_scan_bwd.2 = f32[4,8]": 2.5,
       "%fusion.5 = f32[4,8]": 1.0}


def _module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ssd_kernel_share", harness.BENCH / "metrics" / f"{METRIC}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernels_are_the_mosaic_custom_calls():
    mod = _module()
    assert mod.kernels(TEXT) == {"ssd_scan_fwd.1", "ssd_scan_bwd.2"}
    assert mod.kernels(TEXT.replace('"tpu_custom_call"', '"other"')) == set()


def _read(monkeypatch, text, ops):
    monkeypatch.setattr(scopes, "compiled_step_text",
                        lambda cfg, traffic: text)
    monkeypatch.setattr("sys.argv", ["run.py", "--workload", CELL])
    rec = {"kind": "train", "steps": 2,
           "config": harness.load_cell(CELL)["config"]}
    trace = SimpleNamespace(ops=ops, programs={"jit_train_step": 5.0})
    return harness.load_reader(METRIC)(rec, trace)


def test_share_of_the_scan_scope_in_custom_calls(monkeypatch):
    assert _read(monkeypatch, TEXT, OPS) == pytest.approx(
        100.0 * 3.5 / 4.0)


def test_reads_zero_without_kernels(monkeypatch):
    """As on a step whose scan runs in XLA ops, where the only custom calls
    are XLA's own."""
    text = TEXT.replace('"tpu_custom_call"', '"AssumeGatherIndicesInBound"')
    assert _read(monkeypatch, text, OPS) == 0.0


def test_none_where_the_scope_reading_is_none(monkeypatch):
    unscoped = dict(OPS, **{"%gone.9 = f32[1]": 1.0})    # 20% not in the text
    assert _read(monkeypatch, TEXT, unscoped) is None
    no_scan = {"%fusion.5 = f32[4,8]": 1.0}
    assert _read(monkeypatch, TEXT, no_scan) is None


def test_tiny_step_on_the_cpu_reads_zero(monkeypatch):
    """The cell's step cut to a tiny size: the scan runs in XLA ops on the
    CPU, so no op is a kernel."""
    found = copy.deepcopy(harness.load_cell(CELL))
    found["config"]["model"].update(n_layers=2, d_model=64, vocab_size=512,
                                    ssm_state=16, ssm_head_dim=16,
                                    ssm_chunk=32)
    found["traffic"].update(batch=4, seq=64, vocab_used=500)
    text = scopes.compiled_step_text(found["config"], found["traffic"])
    named = scopes.instruction_scopes(text)
    ops = {f"%{name} = f32[1]": 1e-3 for name, (sc, _) in named.items()
           if sc != scopes.UNSCOPED}
    assert _module().kernels(text) == set()
    assert _read(monkeypatch, text, ops) == 0.0
