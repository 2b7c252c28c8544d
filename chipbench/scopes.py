"""Device time of a compiled program by the named scope of its source.

The chain runtime names its work with ``jax.named_scope`` (``SCOPES`` below:
one flat name per component of the pipeline train step).  The names survive
into the compiled program's text as each instruction's
``metadata={op_name="..."}``, a ``/``-separated path such as

    jit(train_step)/transpose(jvp())/while/body/.../stage/.../ssd_scan/mul

``instruction_scopes(text)`` maps every instruction of that text to a
``(scope, direction)`` pair:

* the scope is the innermost path segment that, with its transformation
  wrappers taken off (``transpose(jvp(head))`` -> ``head``), is one of
  ``SCOPES``; ``"unscoped"`` where none is.  An instruction that names no
  scope itself takes the pair of the instruction that calls its computation
  (a copy XLA adds to a loop body carries no metadata, nor do a fifth of
  the fusions on a v5e);
* the direction is ``"remat"`` where the path holds
  ``rematted_computation`` (the forward recomputed under ``jax.checkpoint``),
  ``"bwd"`` where it holds ``transpose(``, ``"fwd"`` otherwise.

``scope_seconds(ops, scopes)`` adds up a trace's device self seconds per
instruction (``chipbench.trace``'s ``ops``) into seconds per pair.  The list of
names is the benchmark's own, not imported from the program: a scope the
program renames reads as absent, never as time silently moved elsewhere.

``read_scopes(rec, trace)`` is what the per-layer metric files read.  The
harness hands a reader the run's record and its trace reduction, which holds
each op's self time but not the compiled program that names the ops' scopes.
So the first reader of a run lowers and compiles the train driver's step once
more, from abstract arguments of the same shapes and shardings
(``compiled_step_text``): the same program, found in the compile cache that
set-up filled, with the instruction names of the trace.  The cache's key
leaves metadata out, so where it hands back the program compiled from other
scopes (a shared cache that another checkout filled), the reader compiles
it afresh for this source's metadata.  The cell is the one the process
runs, as its command line names it (``--workload``).  The reading is kept in
the record for the other readers, and its table goes to standard error.
"""
from __future__ import annotations

import re
import time
from dataclasses import dataclass

SCOPES = ("embed", "restack", "tick", "stage", "bubble", "ppermute",
          "block_norm", "attn", "mlp", "moe", "rglru", "ssd_in_proj",
          "ssd_conv", "ssd_scan", "ssd_gate_norm", "ssd_out_proj", "head",
          "optimizer")
DIRECTIONS = ("fwd", "bwd", "remat")
UNSCOPED = "unscoped"
MAX_UNSCOPED = 0.02    # above this share of unscoped time a reading is None

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_NUMBER = re.compile(r"\.\d+\b")
_PATH = re.compile(r'(?:op_name=|loc\()"((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)|"
                     r"branch_computations=\{([^}]*)\}")
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def _unwrap(segment: str) -> str:
    while (m := _WRAPPER.match(segment)):
        segment = m.group(1)
    return segment


def scope_of(op_name: str) -> tuple[str, str]:
    """The ``(scope, direction)`` of one ``op_name`` path.  XLA joins the
    paths of merged instructions with ``;``: the first one counts."""
    path = op_name.split(";", 1)[0]
    scope = UNSCOPED
    for segment in path.split("/"):
        name = _unwrap(segment)
        if name in SCOPES:
            scope = name
    if "rematted_computation" in path:
        return scope, "remat"
    return scope, "bwd" if "transpose(" in path else "fwd"


def module_name(text: str) -> str | None:
    """The compiled program's name (``jit_train_step``), as the trace's
    ``XLA Modules`` line gives it."""
    m = _MODULE.match(text)
    return m.group(1) if m else None


def instruction_scopes(text: str) -> dict:
    """``{instruction name: (scope, direction)}`` of a compiled HLO text."""
    own: dict = {}
    home: dict = {}            # instruction -> its computation
    caller: dict = {}          # computation -> the first instruction calling it
    current = None
    for line in text.splitlines():
        if (m := _COMPUTATION.match(line)):
            current = m.group(1)
            continue
        if not (m := _INSTRUCTION.match(line)):
            continue
        name = m.group(1)
        home[name] = current
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else (UNSCOPED, "fwd")
        for a, b in _CALLED.findall(line):
            for comp in (a,) if a else b.split(","):
                caller.setdefault(comp.strip().lstrip("%"), name)

    out: dict = {}

    def resolve(name: str) -> tuple[str, str]:
        if name not in out:
            out[name] = own[name]           # a cycle reads the own pair
            if own[name][0] == UNSCOPED and home[name] in caller:
                out[name] = resolve(caller[home[name]])
        return out[name]

    for name in own:
        resolve(name)
    return out


def instruction(op_key: str) -> str:
    """The instruction name of a trace op (``%fusion.7 = f32[4,8]`` ->
    ``fusion.7``)."""
    return op_key.split(" = ", 1)[0].strip().lstrip("%")


def scope_seconds(ops: dict, scopes: dict) -> tuple[dict, float]:
    """Seconds per ``(scope, direction)`` of the trace ops ``{op key: self
    seconds}``, and the seconds of the ops no instruction of the text names
    (those also count as ``unscoped``)."""
    out: dict = {}
    unmatched = 0.0
    for key, s in ops.items():
        pair = scopes.get(instruction(key))
        if pair is None:
            unmatched += s
            pair = (UNSCOPED, "fwd")
        out[pair] = out.get(pair, 0.0) + s
    return out, unmatched


@dataclass
class ScopeReading:
    """Device self seconds of one program's ops per ``(scope, direction)``
    over a traced window of ``steps`` steps."""
    seconds: dict
    unmatched: float
    steps: int

    @property
    def total(self) -> float:
        return sum(self.seconds.values())

    def share(self, scope: str | None = None,
              direction: str | None = None) -> float:
        """The share of the total that a scope, a direction or both take."""
        part = sum(s for (sc, d), s in self.seconds.items()
                   if scope in (None, sc) and direction in (None, d))
        return part / self.total if self.total > 0 else 0.0

    def sound(self) -> bool:
        return self.total > 0 and self.share(UNSCOPED) <= MAX_UNSCOPED

    def ms_per_step(self, scopes: tuple) -> float | None:
        """Device ms a step of the ops under ``scopes``, every direction;
        None where no op is under them or too much time is unscoped."""
        got = [s for (sc, _), s in self.seconds.items() if sc in scopes]
        if not got or not self.sound():
            return None
        return 1e3 * sum(got) / self.steps

    def table(self) -> str:
        rows = [f"{'scope':<14}" + "".join(f"{d:>10}" for d in DIRECTIONS)
                + f"{'all':>10}   (device ms a step)"]
        for sc in SCOPES + (UNSCOPED,):
            ms = [1e3 * self.seconds.get((sc, d), 0.0) / self.steps
                  for d in DIRECTIONS]
            if any(ms):
                rows.append(f"{sc:<14}" + "".join(f"{x:10.3f}" for x in ms)
                            + f"{sum(ms):10.3f}")
        rows.append(f"total {1e3 * self.total / self.steps:.3f} ms a step "
                    f"over {self.steps} steps; unscoped "
                    f"{100 * self.share(UNSCOPED):.3f}%, of which not in the "
                    f"compiled text {1e3 * self.unmatched / self.steps:.3f} "
                    f"ms a step")
        return "\n".join(rows)


def compiled_step_text(cfg: dict, traffic: dict) -> str:
    """The compiled HLO text of the step ``chipbench/drivers/train.py``
    builds from these files, as its window runs it, lowered from abstract
    arguments."""
    import importlib

    import jax

    from chipbench.drivers.train import model_config
    from repro.msl import plan_on_devices
    from repro.msl.pipeline import make_pipeline_train_step
    from repro.optim import adamw, cosine_schedule

    mcfg = model_config(cfg)
    B, S, M = traffic["batch"], traffic["seq"], traffic["n_micro"]
    plan, mesh = plan_on_devices(mcfg, traffic["stages"], seq_len=S,
                                 microbatch=B // M)
    o = cfg["optimizer"]
    opt = adamw(cosine_schedule(o["lr"], o["warmup"], o["total"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    model = importlib.import_module(f"chipbench.models.{traffic['model']}")
    params = jax.eval_shape(lambda: model.init_params(cfg["model"], 0))
    state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda: model.make_batches(
        cfg["model"], 0, 1, B, S, traffic["vocab_used"])[0])

    def lower(params, state):
        step = jax.jit(make_pipeline_train_step(mcfg, mesh, plan, M, opt),
                       donate_argnums=(0, 1))
        return step.lower(params, state, batch)

    def placed(tree, sh):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, sh)

    # the window feeds the step its own outputs, whose shardings make a
    # second program beside the one set-up's first step (fresh weights) ran
    shardings = lower(params, state).compile().output_shardings
    params, state = placed(params, shardings[0]), placed(state, shardings[1])
    lowered = lower(params, state)
    text = lowered.compile().as_text()
    if named_scopes(text) != named_scopes(lowered.as_text(debug_info=True)):
        # the compile cache keys a program without its metadata, so it can
        # hand back the same program compiled from a source whose scopes
        # differ (another checkout's), and that program ran: give its
        # instructions the metadata of a compile of this source
        from chipbench.harness import log

        log("scopes: the compile cache held this program compiled from "
            "other scopes; compiling it afresh for its metadata")
        text = with_metadata(text, _uncached(
            lambda: lower(params, state).compile().as_text()))
    return text


def with_metadata(text: str, source: str) -> str:
    """``text`` with each instruction's metadata taken from the instruction
    in the same place of ``source``, a compile of the same program from
    other sources: the same instructions in the same order, some numbered
    differently.  Raises ValueError where the programs differ."""
    def shape(lines):
        return [_NUMBER.sub("", _METADATA.sub("", x)) for x in lines]

    ran = [x for x in text.splitlines() if _INSTRUCTION.match(x)]
    src = [x for x in source.splitlines() if _INSTRUCTION.match(x)]
    if shape(ran) != shape(src):
        raise ValueError("the cached program is not the one compiled")
    meta = iter(m.group(0) if (m := _METADATA.search(x)) else "" for x in src)
    out = []
    for line in text.splitlines():
        if _INSTRUCTION.match(line):
            line = _METADATA.sub("", line) + next(meta)
        out.append(line)
    return "\n".join(out)


def named_scopes(text: str) -> set:
    """The scopes of ``SCOPES`` that a compiled HLO text (``op_name``) or a
    lowered module's debug text (``loc``) names."""
    return {scope_of(p)[0] for p in _PATH.findall(text)} - {UNSCOPED}


def _uncached(compile_text):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return compile_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def running_cell() -> str | None:
    """The cell this process runs, as its command line names it."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    return ap.parse_known_args(sys.argv[1:])[0].workload


def reading(ops: dict, programs: dict, text: str,
            steps: int) -> ScopeReading | None:
    """The scope reading of a trace's ops, where the compiled program of
    ``text`` is the only one the trace holds (the ops of several programs
    are not told apart)."""
    if set(programs) != {module_name(text)}:
        return None
    seconds, unmatched = scope_seconds(ops, instruction_scopes(text))
    return ScopeReading(seconds, unmatched, steps)


def read_scopes(rec: dict, trace) -> ScopeReading | None:
    """The run's scope reading (see the module's docstring), or None."""
    if "scope_reading" not in rec:
        rec["scope_reading"] = _read_scopes(rec, trace)
    return rec["scope_reading"]


def _read_scopes(rec: dict, trace) -> ScopeReading | None:
    from chipbench import harness

    cell = running_cell()
    if trace is None or rec.get("kind") != "train" or cell is None:
        return None
    found = harness.load_cell(cell)
    if found["config"] != rec["config"]:
        return None
    t0 = time.perf_counter()
    try:
        text = compiled_step_text(found["config"], found["traffic"])
    except ValueError as e:
        harness.log(f"scopes: no compiled text of the step: {e}")
        return None
    harness.log(f"scopes: compiled text of the step in "
                f"{time.perf_counter() - t0:.1f} s")
    got = reading(trace.ops, trace.programs, text, rec["steps"])
    if got is None:
        harness.log(f"scopes: the trace holds programs {sorted(trace.programs)}"
                    f", not {module_name(text)} alone")
    else:
        harness.log("scopes:\n" + got.table())
    return got
