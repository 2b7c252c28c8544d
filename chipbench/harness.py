"""The benchmark harness: one cell of ``BENCHMARK.json`` on this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  The cell's entry in ``BENCHMARK.json`` names a
configuration (``chipbench/configs/<config>.json``) and a traffic file
(``chipbench/traffic/<traffic>.json``); the traffic file names its driver
(``chipbench/drivers/<driver>.py``), which builds the system from the
configuration, warms it up, runs the measured window and checks its answers
against the plain reference, within the limits the configuration states.  Each per-layer metric is read by its own file,
``chipbench/metrics/<metric>.py``, a function ``read(record, trace)`` that
returns a number or None.

The run is one process.  Set-up (imports, JAX start-up, building the system,
warm-up and every compile or cache load) is ``setup_s``; then the window runs
for ``--seconds`` with the profiler off (``--trace 0``, end-to-end metrics)
or on (``--trace 1``, per-layer metrics).  The last line of standard output is
the result; the compared numbers with their limits end standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANS = ("train_step",)   # the harness's host spans


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    """The cell's entries and data files, all found by name."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported
                              else [])]
    return {"cell": cell, "end_to_end": e2e, "per_layer": layer,
            **load_files(cell["config"], cell["traffic"])}


def load_files(config: str, traffic: str) -> dict:
    """A configuration's and a traffic mix's data files, by name."""
    return {"config": load_json(BENCH / "configs" / f"{config}.json"),
            "traffic": load_json(BENCH / "traffic" / f"{traffic}.json")}


def driver_class(traffic: dict):
    return importlib.import_module(
        f"chipbench.drivers.{traffic['driver']}").CELL


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def accelerator(chips: int):
    """The devices this cell runs on, or None (with the reason) when JAX
    finds no accelerator or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        return None, "no accelerator found (JAX platform cpu)"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return devs[:chips], ""


def device_info(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def run_cell(args, t_start: float, devs, found: dict | None = None) -> dict:
    """Set-up, window, per-layer readings and the check of one run.
    ``found`` replaces the cell's files (tests run shrunken copies)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    found = found or load_cell(args.workload)
    cls = driver_class(found["traffic"])
    counter = CompileCounter()
    cell = cls(found["config"], found["traffic"], args.seed, args.seconds)
    limits = cell.limits
    setup_s = time.perf_counter() - t_start

    tdir = None
    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    counter.active = True
    rec = cell.run_window(annotate)
    counter.active = False
    rec["device_kind"] = devs[0].device_kind if devs else "cpu"
    rec["chips"] = len(devs) if devs else 1
    trace = None
    if tdir is not None:
        jax.profiler.stop_trace()
        from chipbench.trace import find_xplane, reduce_trace

        try:
            trace = reduce_trace(find_xplane(tdir), SPANS)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    device = device_info(devs) if devs else {}
    log(f"compiles in window: {json.dumps(counter.counts)}")
    log(f"run: {json.dumps(cell.notes(rec), default=float)}")

    if args.trace:
        metrics = {}
        for m in found["per_layer"]:
            v = load_reader(m["name"])(rec, trace)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = cell.end_to_end(rec)
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in found["end_to_end"]}
    t_check = time.perf_counter()
    nums, detail = cell.check(rec)
    log(f"check: {json.dumps(detail, default=str)} "
        f"({time.perf_counter() - t_check:.1f} s)")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    result = {
        "correct": all(v <= limits[k] for k, v in nums.items()),
        "attempted": rec["offered"],
        "failed": rec.get("failed", 0),
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        result["device"].update(busy_s=trace.busy_s, window_s=trace.window_s)
        result["breakdown"] = {
            "device_ops": sorted(trace.ops.items(), key=lambda x: -x[1])[:10],
            "idle_gaps": sorted(trace.gaps, key=lambda x: -x[1])[:10]}
        log(f"trace: programs {json.dumps(trace.programs)}; idle by host "
            f"span {json.dumps(trace.idle_by_label())}")
    result["compiles_in_window"] = counter.counts
    result["checks"] = checks   # the compared numbers come last
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        import repro  # noqa: F401
    except ImportError:
        log(f"chipbench: the system under test (src/repro under {ROOT}) "
            f"is not here")
        return 2
    cell = load_cell(args.workload)["cell"]
    devs, why = accelerator(cell["chips"])
    if devs is None:
        log(f"chipbench: {why}")
        return 1
    result = run_cell(args, t_start, devs)
    for k, c in result["checks"].items():
        log(f"compared {k}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result))
    return 0
