#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --seconds <s> [--control 3] [--fault <name>] [--config <name>] \\
        [--decayed leaf,leaf,...]

One process.  For each seed it builds the cell, runs a window of ``--seconds``
and prints one JSON line: the compared numbers of the program (the lower
reading), and for the first ``--control`` seeds those of the control, the
plain reference in the precision below the configuration's in the program's
place (the upper reading).  ``--fault`` plants one of ``chipbench/faults.py``
under the timed path instead.  ``--config`` runs the cell's traffic on
another configuration file, and ``--decayed`` compares the program also with
a reference that decays those leaves (the witness of a departure in weight
decay).  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--config", default=None)
    ap.add_argument("--decayed", default=None,
                    type=lambda s: s.split(","))
    args = ap.parse_args(argv)
    found = harness.load_cell(args.workload)
    if args.config:
        found.update(harness.load_files(args.config,
                                        found["cell"]["traffic"]))
    devs, why = harness.accelerator(found["cell"]["chips"])
    if devs is None:
        harness.log(f"calibrate: {why}")
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    from chipbench import faults

    enable_compile_cache()
    cls = harness.driver_class(found["traffic"])
    plant = (faults.FAULTS[found["traffic"]["driver"]][args.fault]
             if args.fault else contextlib.nullcontext)
    null = lambda name: contextlib.nullcontext()  # noqa: E731
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        with plant():
            cell = cls(found["config"], found["traffic"], seed, args.seconds)
            rec = cell.run_window(null)
        out = {"seed": seed, "fault": args.fault,
               "setup_s": time.perf_counter() - t0,
               "end_to_end": cell.end_to_end(rec)}
        out["program"], detail = cell.check(rec)
        out["program_detail"] = detail
        if i < args.control:
            out["control"], out["control_detail"] = cell.check(rec,
                                                               control=True)
        if args.decayed:
            out["witness"], out["witness_detail"] = cell.check(
                rec, decayed=args.decayed)
        out["config"] = found["config"]["name"]
        out["limits"] = cell.limits
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out, default=str), flush=True)
        del cell, rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
