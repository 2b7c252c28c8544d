"""The attention kernel share (``metrics/attn_kernel_share.train.py``):
Mosaic kernels that the compiled text bills to the ``attn`` scope, their
share of that scope's time; 0.0 with no Mosaic kernel in ``attn`` (kernels
of other scopes do not count), None where the scope reading is None."""
import copy
from types import SimpleNamespace

import pytest

from chipbench import harness, scopes

CELL = "train-nemotron3-nano-ep16-s4096-k1"
METRIC = "attn_kernel_share.train"
STAGE = "jit(train_step)/jvp()/while/body/stage/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp())/while/body/stage/while/body/closed_call"
REMAT = ("jit(train_step)/transpose(jvp())/while/body/rematted_computation/"
         "stage/while/body/closed_call")

TEXT = f"""\
HloModule jit_train_step, is_scheduled=true

ENTRY %main (a: bf16[4,8]) -> bf16[4,8] {{
  %a = bf16[4,8]{{1,0}} parameter(0)
  %dot.3 = bf16[4,8]{{1,0}} convolution(%a, %a), dim_labels=bf_io->bf, metadata={{op_name="{STAGE}/attn/dot_general"}}
  %flash_attention_fwd.1 = (bf16[4,8]{{1,0}}, f32[4]{{0}}) custom-call(%a, %dot.3), custom_call_target="tpu_custom_call", metadata={{op_name="{REMAT}/attn/jit(flash_attention)/pallas_call"}}
  %flash_attention_bwd.2 = bf16[4,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}/attn/pallas_call"}}
  %ssd_scan_fwd.7 = bf16[4,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STAGE}/ssd_scan/pallas_call"}}
  %custom-call.6 = s32[4]{{0}} custom-call(%a), custom_call_target="AssumeGatherIndicesInBound", metadata={{op_name="{STAGE}/attn/gather"}}
  %gte.4 = bf16[4,8]{{1,0}} get-tuple-element(%flash_attention_fwd.1), index=0
  ROOT %fusion.5 = bf16[4,8]{{1,0}} fusion(%gte.4), kind=kLoop, calls=%fused, metadata={{op_name="jit(train_step)/optimizer/mul"}}
}}
"""
OPS = {"%dot.3 = bf16[4,8]": 0.75, "%custom-call.6 = s32[4]": 0.25,
       "%flash_attention_fwd.1 = (bf16[4,8]": 1.0,
       "%flash_attention_bwd.2 = bf16[4,8]": 2.0,
       "%ssd_scan_fwd.7 = bf16[4,8]": 1.5, "%fusion.5 = bf16[4,8]": 1.0}


def _module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "attn_kernel_share", harness.BENCH / "metrics" / f"{METRIC}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(monkeypatch, text, ops):
    monkeypatch.setattr(scopes, "compiled_step_text",
                        lambda cfg, traffic: text)
    monkeypatch.setattr("sys.argv", ["run.py", "--workload", CELL])
    rec = {"kind": "train", "steps": 2,
           "config": harness.load_cell(CELL)["config"]}
    trace = SimpleNamespace(ops=ops, programs={"jit_train_step": 6.5})
    return harness.load_reader(METRIC)(rec, trace)


def test_kernels_are_the_mosaic_custom_calls():
    assert _module().kernels(TEXT) == {"flash_attention_fwd.1",
                                       "flash_attention_bwd.2",
                                       "ssd_scan_fwd.7"}


def test_share_of_the_attn_scope_in_its_kernels(monkeypatch):
    """Both kernels, in the recompute and the backward, over the scope's
    4.0 s; the SSD kernel and XLA's own custom call are not counted."""
    assert _read(monkeypatch, TEXT, OPS) == pytest.approx(100.0 * 3.0 / 4.0)


@pytest.mark.parametrize("target", ["AssumeGatherIndicesInBound", "other"])
def test_reads_zero_without_a_kernel_in_attn(monkeypatch, target):
    """As at the parent, whose attention runs in XLA ops: the only Mosaic
    kernel is the SSD scan's, in its own scope."""
    text = TEXT.replace('custom-call(%a, %dot.3), custom_call_target='
                        '"tpu_custom_call"',
                        f'custom-call(%a, %dot.3), custom_call_target="{target}"')
    text = text.replace('custom-call(%a), custom_call_target="tpu_custom_call"'
                        ', metadata={op_name="' + BWD,
                        f'custom-call(%a), custom_call_target="{target}", '
                        'metadata={op_name="' + BWD)
    assert _module().kernels(text) == {"ssd_scan_fwd.7"}
    assert _read(monkeypatch, text, OPS) == 0.0


def test_none_where_the_scope_reading_is_none(monkeypatch):
    unscoped = dict(OPS, **{"%gone.9 = f32[1]": 1.0})    # not in the text
    assert _read(monkeypatch, TEXT, unscoped) is None
    no_attn = {"%fusion.5 = bf16[4,8]": 1.0}
    assert _read(monkeypatch, TEXT, no_attn) is None


def test_tiny_step_on_the_cpu_reads_zero(monkeypatch):
    """The cell's step cut to a tiny size: on the CPU attention runs in XLA
    ops, so no op of ``attn`` is a kernel."""
    found = copy.deepcopy(harness.load_cell(CELL))
    found["config"]["model"].update(
        d_model=64, vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
        ssm_state=16, ssm_head_dim=16, ssm_heads=8, ssm_groups=2,
        ssm_chunk=16, n_experts=16, moe_experts_held=4, moe_d_ff=32,
        moe_shared_d_ff=48, loss_chunk=128, q_chunk=16)
    found["traffic"].update(batch=4, seq=64, n_micro=2, vocab_used=256)
    text = scopes.compiled_step_text(found["config"], found["traffic"])
    named = scopes.instruction_scopes(text)
    assert "attn" in {sc for sc, _ in named.values()}
    ops = {f"%{name} = f32[1]": 1e-3 for name, (sc, _) in named.items()
           if sc != scopes.UNSCOPED}
    assert _module().kernels(text) == set()
    assert _read(monkeypatch, text, ops) == 0.0
