"""The reduction of device time to named scopes: on synthetic HLO lines and
ops, on the compiled text of a tiny train step, and on a small trace of the
train step recorded on the chip with its compiled text."""
import copy
import gzip
import re
from pathlib import Path

import pytest

from chipbench import harness, scopes

HERE = Path(__file__).resolve().parent
RECORDED = HERE.parent / "testdata" / "train_scopes_v5e.xplane.pb"
RECORDED_TEXT = HERE.parent / "testdata" / "train_scopes_v5e.hlo.txt.gz"
RECORDED_STEPS = 3
CELL = "train-mamba2-370m-k1"
METRICS = ("ssd_scan_ms.train", "ssd_proj_ms.train", "layer_scan_ms.train",
           "optimizer_ms.train", "remat_share.train")


@pytest.mark.parametrize("op_name,pair", [
    ("jit(train_step)/jvp(embed)/jit(_take)/add", ("embed", "fwd")),
    ("jit(train_step)/transpose(jvp(restack))/reshape", ("restack", "bwd")),
    ("jit(train_step)/jvp()/while/body/cond/branch_1_fun/stage/while/body/"
     "closed_call/ssd_scan/bcqn,bckn->bcqk/dot_general", ("ssd_scan", "fwd")),
    ("jit(train_step)/transpose(jvp())/while/body/stage/while/body/"
     "closed_call/checkpoint/rematted_computation/ssd_conv/jit(silu)/mul",
     ("ssd_conv", "remat")),
    ("jit(train_step)/transpose(jvp())/while/body/stage/while/body/"
     "closed_call/checkpoint/ssd_out_proj/dot_general",
     ("ssd_out_proj", "bwd")),
    ("jit(train_step)/transpose(jvp())/while/body/stage/while/body/"
     "dynamic_update_slice", ("stage", "bwd")),
    ("jit(train_step)/optimizer/mul", ("optimizer", "fwd")),
    ("jit(train_step)/jvp()/while/body/dynamic_update_slice",
     (scopes.UNSCOPED, "fwd")),
    ("jit(train_step)/jvp(stages)/mul", (scopes.UNSCOPED, "fwd")),
    ("jit(train_step)/transpose(jvp(head))/reshape;"
     "jit(train_step)/optimizer/add", ("head", "bwd")),
])
def test_scope_is_the_innermost_known_segment_unwrapped(op_name, pair):
    assert scopes.scope_of(op_name) == pair


SYNTHETIC = """\
HloModule jit_train_step, is_scheduled=true

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(train_step)/transpose(jvp(head))/mul"}
  %n = f32[4]{0} negate(%m), metadata={op_name="jit(train_step)/transpose(jvp(head))/neg"}
  ROOT %e = f32[4]{0} exponential(%n), metadata={op_name="jit(train_step)/optimizer/exp"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %copy.3 = f32[4]{0} copy(%x)
  %fusion.5 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_a
  %add.1 = f32[4]{0} add(%x, %x), metadata={op_name="jit(train_step)/jvp()/while/body/stage/while/body/closed_call/ssd_scan/add"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%i, %add.1)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] compare(%i, %i), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_a
  %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_a, metadata={op_name="jit(train_step)/embed/add"}
  %while.1 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp()/while/body/stage/while"}
  ROOT %copy.9 = f32[4]{0} copy(%fusion.1)
}
"""


def test_instructions_take_their_own_else_their_callers_scope():
    got = scopes.instruction_scopes(SYNTHETIC)
    assert scopes.module_name(SYNTHETIC) == "jit_train_step"
    assert got["fusion.2"] == ("embed", "fwd")          # its own metadata
    assert got["m"] == ("head", "bwd")
    assert got["p0"] == ("stage", "fwd")    # the first fusion calling it
    assert got["add.1"] == ("ssd_scan", "fwd")
    assert got["while.1"] == ("stage", "fwd")
    assert got["copy.3"] == ("stage", "fwd")            # its loop's caller
    assert got["fusion.5"] == ("stage", "fwd")
    assert got["lt"] == ("stage", "fwd")
    assert got["fusion.1"] == (scopes.UNSCOPED, "fwd")  # nothing names it
    assert got["copy.9"] == (scopes.UNSCOPED, "fwd")


def test_ops_sum_per_pair_and_unmatched_ops_count_unscoped():
    text_scopes = scopes.instruction_scopes(SYNTHETIC)
    ops = {"%fusion.2 = f32[4]": 1.0, "%fusion.5 = f32[4]": 2.5,
           "%add.1 = f32[4]": 4.0, "%copy.9 = f32[4]": 0.25,
           "%gone.7 = f32[4]": 0.125}
    got, unmatched = scopes.scope_seconds(ops, text_scopes)
    assert got == {("embed", "fwd"): 1.0, ("stage", "fwd"): 2.5,
                   ("ssd_scan", "fwd"): 4.0,
                   (scopes.UNSCOPED, "fwd"): 0.375}
    assert unmatched == 0.125


def test_reading_needs_the_program_alone_in_the_trace():
    ops = {"%add.1 = f32[4]": 1.0}
    assert scopes.reading(ops, {"jit_train_step": 1.0}, SYNTHETIC, 1)
    assert scopes.reading(ops, {"jit_train_step": 1.0, "jit_other": 0.1},
                          SYNTHETIC, 1) is None
    assert scopes.reading(ops, {"jit_other": 1.0}, SYNTHETIC, 1) is None


def test_readings_are_none_where_a_scope_is_absent_or_time_unscoped():
    seconds = {("ssd_scan", "fwd"): 0.3, ("ssd_scan", "remat"): 0.2,
               ("ssd_scan", "bwd"): 0.4, ("optimizer", "fwd"): 0.09,
               (scopes.UNSCOPED, "fwd"): 0.01}
    r = scopes.ScopeReading(seconds, 0.0, 2)
    assert r.ms_per_step(("ssd_scan",)) == pytest.approx(450.0)
    assert r.ms_per_step(("ssd_in_proj", "ssd_out_proj")) is None
    assert r.share(direction="remat") == pytest.approx(0.2)
    assert r.sound()
    seconds[(scopes.UNSCOPED, "bwd")] = 0.03        # 4% unscoped
    assert scopes.ScopeReading(seconds, 0.0, 2).ms_per_step(
        ("ssd_scan",)) is None
    assert "ssd_scan" in r.table()


def _tiny(found):
    found["config"]["model"].update(n_layers=2, d_model=64, vocab_size=512,
                                    ssm_state=16, ssm_head_dim=16,
                                    ssm_chunk=32)
    found["traffic"].update(batch=4, seq=64, vocab_used=500)


@pytest.fixture(scope="module")
def tiny_cell():
    """The train driver's cell, shrunken, after its set-up on the CPU, and
    the compiled text the readers build for the same files."""
    found = copy.deepcopy(harness.load_cell(CELL))
    _tiny(found)
    cell = harness.driver_class(found["traffic"])(
        found["config"], found["traffic"], 2**31 + 3, 0)
    return cell, scopes.compiled_step_text(found["config"], found["traffic"])


def _instructions(text):
    """The instruction lines of a compiled text, metadata stripped (it holds
    the call site's source lines, which differ)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines() if re.match(r"^\s+(ROOT )?%", line)]


def test_the_readers_compile_the_program_the_window_runs(tiny_cell):
    cell, text = tiny_cell
    ran = cell.step.lower(cell.params, cell.opt_state,
                          cell.batches[0]).compile().as_text()
    assert _instructions(text) == _instructions(ran)
    assert scopes.instruction_scopes(text) == scopes.instruction_scopes(ran)


@pytest.mark.parametrize("scope", ["embed", "restack", "tick", "stage",
                                   "block_norm",
                                   "ssd_in_proj", "ssd_conv", "ssd_scan",
                                   "ssd_gate_norm", "ssd_out_proj", "head",
                                   "optimizer"])
def test_tiny_step_text_names_every_ssd_scope(tiny_cell, scope):
    pairs = set(scopes.instruction_scopes(tiny_cell[1]).values())
    assert any(sc == scope for sc, _ in pairs)
    if scope == "ssd_scan":
        assert {d for sc, d in pairs if sc == scope} == set(scopes.DIRECTIONS)


@pytest.fixture
def compile_cache(tmp_path):
    """A persistent compile cache that keeps even a tiny program."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), True, 0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_cached_program_of_other_scopes_is_compiled_afresh(
        compile_cache, monkeypatch):
    """The cache's key leaves out metadata: a checkout without the scopes
    fills it with the same program, and the readers still see the scopes."""
    import contextlib

    import jax

    found = copy.deepcopy(harness.load_cell(CELL))
    _tiny(found)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = scopes.compiled_step_text(found["config"], found["traffic"])
    assert scopes.named_scopes(bare) == {"ppermute"}   # the primitive's name
    text = scopes.compiled_step_text(found["config"], found["traffic"])
    assert {"ssd_scan", "stage", "optimizer"} <= scopes.named_scopes(text)
    assert _instructions(text) == _instructions(bare)   # the program that ran
    fresh = scopes.instruction_scopes(scopes._uncached(
        lambda: scopes.compiled_step_text(found["config"], found["traffic"])))
    assert (sorted(scopes.instruction_scopes(text).values())
            == sorted(fresh.values()))


def test_metadata_moves_by_place_and_only_onto_the_same_program():
    ran = SYNTHETIC.replace("%add.1", "%add.4").replace(
        ', metadata={op_name="jit(train_step)/jvp()/while/body/stage/while/'
        'body/closed_call/ssd_scan/add"}', "")
    got = scopes.instruction_scopes(scopes.with_metadata(ran, SYNTHETIC))
    assert got["add.4"] == ("ssd_scan", "fwd")
    assert got == {("add.4" if k == "add.1" else k): v for k, v in
                   scopes.instruction_scopes(SYNTHETIC).items()}
    with pytest.raises(ValueError):
        scopes.with_metadata(ran.replace("negate(", "abs("), SYNTHETIC)


def test_the_five_readers_share_one_compile_of_the_running_cell(
        tiny_cell, monkeypatch):
    from types import SimpleNamespace

    text = tiny_cell[1]
    named = scopes.instruction_scopes(text)
    ops = {f"%{name} = f32[1]": 1e-3 for name, (sc, _) in named.items()
           if sc != scopes.UNSCOPED}
    trace = SimpleNamespace(ops=ops, programs={"jit_train_step": 1.0})
    calls = []
    monkeypatch.setattr(scopes, "compiled_step_text",
                        lambda cfg, traffic: calls.append(cfg) or text)
    def record():
        return {"kind": "train", "steps": 2,
                "config": harness.load_cell(CELL)["config"]}

    rec = record()
    monkeypatch.setattr("sys.argv", ["run.py", "--workload", CELL])
    got = {m: harness.load_reader(m)(rec, trace) for m in METRICS}
    assert len(calls) == 1
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["remat_share.train"] < 100.0
    monkeypatch.setattr("sys.argv", ["pytest"])      # no cell named: None
    assert harness.load_reader(METRICS[0])(record(), trace) is None


@pytest.fixture(scope="module")
def recorded():
    """Three traced steps of the cell cut to 2 layers (every width as the
    cell has it) on a v5e chip, and the step's compiled text, recorded by
    ``chipbench/record_trace.py``.  The trace's ``/host:metadata`` plane,
    which the reduction never reads, was emptied to keep the file small."""
    from chipbench import trace

    with gzip.open(RECORDED_TEXT, "rt") as f:
        text = f.read()
    return trace.reduce_trace(str(RECORDED), harness.SPANS), text


def test_recorded_ops_map_to_the_text_and_add_up_to_the_program(recorded):
    red, text = recorded
    total = sum(red.ops.values())
    seconds, unmatched = scopes.scope_seconds(
        red.ops, scopes.instruction_scopes(text))
    assert set(red.programs) == {scopes.module_name(text)}
    assert unmatched <= 1e-3 * total
    assert sum(seconds.values()) == pytest.approx(total, rel=1e-12)
    assert total == pytest.approx(red.programs["jit_train_step"], rel=1e-3)


def test_recorded_trace_reads_every_metric(recorded):
    red, text = recorded
    got = scopes.reading(red.ops, red.programs, text, RECORDED_STEPS)
    assert got.share(scopes.UNSCOPED) <= scopes.MAX_UNSCOPED
    assert {d for (sc, d) in got.seconds if sc == "ssd_scan"} == set(
        scopes.DIRECTIONS)
    rec = {"scope_reading": got}
    values = {m: harness.load_reader(m)(rec, red) for m in METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    step_ms = 1e3 * got.total / RECORDED_STEPS
    assert sum(values[m] for m in METRICS[:4]) < step_ms
    assert values["remat_share.train"] < 100.0
