#!/usr/bin/env python3
"""Record the small traced train step that ``chipbench/tests`` read, on the
chip.

    python chipbench/record_trace.py --out chipbench/testdata [--layers 2] \\
        [--steps 3]

One process.  It builds the cell ``train-mamba2-370m-k1`` cut to ``--layers``
layers (every width, the batch and the sequence as the cell has them) through
the train driver, whose set-up compiles the step, then traces ``--steps``
more steps under the harness's span ``train_step`` with the harness's
profiler options.  It writes the trace (``train_scopes_v5e.xplane.pb``) and
the step's compiled text, as ``chipbench.scopes.compiled_step_text`` gives it
(``train_scopes_v5e.hlo.txt.gz``), and prints the scope table.  The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gzip  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness, scopes, trace  # noqa: E402

CELL = "train-mamba2-370m-k1"
STEM = "train_scopes_v5e"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, required=True)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs, why = harness.accelerator(1)
    if devs is None:
        harness.log(f"record_trace: {why}")
        return 1
    enable_compile_cache()
    found = copy.deepcopy(harness.load_cell(CELL))
    found["config"]["model"]["n_layers"] = args.layers
    cell = harness.driver_class(found["traffic"])(
        found["config"], found["traffic"], args.seed, 0)
    params, opt_state = cell.params, cell.opt_state

    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for i in range(args.steps):
        with jax.profiler.TraceAnnotation("train_step"):
            params, opt_state, m = cell.step(params, opt_state,
                                             cell.batches[i])
    m["loss"].block_until_ready()
    jax.profiler.stop_trace()

    args.out.mkdir(parents=True, exist_ok=True)
    xplane = args.out / f"{STEM}.xplane.pb"
    try:
        shutil.copy(trace.find_xplane(tdir), xplane)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    text = scopes.compiled_step_text(found["config"], found["traffic"])
    with gzip.open(args.out / f"{STEM}.hlo.txt.gz", "wt") as f:
        f.write(text)

    red = trace.reduce_trace(str(xplane), harness.SPANS)
    got = scopes.reading(red.ops, red.programs, text, args.steps)
    print(f"programs {red.programs}; busy {red.busy_s:.6f} s of "
          f"{red.window_s:.6f} s; {time.perf_counter() - T_START:.1f} s")
    print(got.table() if got else "no reading: other programs in the trace")
    return 0


if __name__ == "__main__":
    sys.exit(main())
