"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

Everything that belongs to a configuration, a traffic mix or a
per-layer metric is found by name:

* ``workloads/<cell>.json`` — the cell: its configuration, its traffic
  mix and the mix's parameters;
* ``configs/<config>.json`` — the deployment, naming its generator
  (``generators/<name>.py``) and its plain reference
  (``reference/<name>.py``);
* ``traffic/<mix>.py`` — the one generator of that mix, a ``Traffic``
  class;
* ``metrics/<metric>.py`` — one reader per per-layer metric, a
  ``read(run)`` function that returns a number or None.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(Exception):
    """The run cannot be made here: no result is printed."""


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module.  A metric's name may
    hold dots, so its file is loaded by path."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind} file {path}")
    if name.isidentifier():
        return importlib.import_module(f"{kind}.{name}")
    mod_name = f"{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """What a traffic mix is handed: the cell's and the configuration's
    files, the run's options, and the shared tools."""

    def __init__(self, name: str, seed: int, seconds: float, trace: bool):
        try:
            self.benchmark = load_json(os.pardir, "BENCHMARK.json")
        except FileNotFoundError as e:
            raise Refused(f"no BENCHMARK.json: {e}") from e
        try:
            self.workload = load_json("workloads", f"{name}.json")
        except FileNotFoundError as e:
            raise Refused(f"no workload {name!r}: {e}") from e
        self.name = name
        if BENCH_DIR not in sys.path:
            sys.path.insert(0, BENCH_DIR)
        self.config = load_json("configs", f"{self.workload['config']}.json")
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.params = self.workload["params"]
        self.generator = load_module("generators", self.config["generator"])
        self.reference = load_module("reference", self.config["reference"])
        self.compile_log = None  # set once JAX is up
        self.scratch = tempfile.mkdtemp(prefix="vtpu-bench-")

    def e2e_names(self):
        return [
            m["name"] for m in self.benchmark["end_to_end"]
            if self.name in m.get("workloads", [self.name])
        ]

    def per_layer(self):
        e2e = set(self.e2e_names())
        out = []
        for m in self.benchmark["per_layer"]:
            listed = m.get("workloads")
            if (self.name in listed) if listed is not None else (m["moves"] in e2e):
                out.append(m)
        return out


def log(kind: str, **fields) -> None:
    """An informational line on stdout (never the last one)."""
    print(f"bench {kind} " + json.dumps(fields, default=float), flush=True)


def import_program() -> None:
    """The system under test from this checkout, nowhere else."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    try:
        import volcano_tpu
    except ImportError as e:
        raise Refused(f"volcano_tpu is not in this checkout: {e}") from e
    if not os.path.abspath(volcano_tpu.__file__).startswith(ROOT + os.sep):
        raise Refused("volcano_tpu imported from outside the checkout")


def setup_jax() -> None:
    """Persistent compile cache at a fixed path inside the checkout, for
    every program however quick to compile.  Before JAX starts."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: JAX's LRU bookkeeping (an -atime file per entry) lost
    # an entry's stamp on the chip and then refused every later write
    jax.config.update("jax_compilation_cache_max_size", -1)


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_accelerator: bool = True, hook=None) -> dict:
    """One run; returns the result object.  ``require_accelerator=False``
    is for the rehearsals on the CPU (benchmark/tests), where ``hook``
    may break the timed path underneath."""
    import_program()
    cell = Cell(name, seed, seconds, trace)
    try:
        return _run(cell, t_start, require_accelerator, hook)
    finally:
        shutil.rmtree(cell.scratch, ignore_errors=True)


def _run(cell: Cell, t_start: float, require_accelerator: bool, hook) -> dict:
    trace = cell.trace
    setup_jax()
    from harness import device
    from harness.compile_log import CompileLog

    if require_accelerator:
        try:
            devices = device.accelerator(cell.workload["chips"])
        except device.NoAccelerator as e:
            raise Refused(str(e)) from e
    else:
        import jax

        devices = jax.devices()[: cell.workload["chips"]]
    cell.compile_log = CompileLog()
    traffic_mod = load_module("traffic", cell.workload["traffic"])
    traffic = traffic_mod.Traffic(cell, require_pallas=require_accelerator)
    if hook is not None:
        hook(traffic)

    traffic.setup()
    setup_s = time.perf_counter() - t_start
    log("setup", setup_s=setup_s, **traffic.setup_info())

    trace_summary = None
    if trace:
        import jax

        from harness import trace as tr

        trace_dir = os.path.join(cell.scratch, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python calls: millions of events a cycle
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("window"):
                traffic.window()
        finally:
            jax.profiler.stop_trace()
    else:
        traffic.window()
    traffic.after_window()
    peak = device.memory_peak_bytes(devices) if require_accelerator else 0
    if trace:
        trace_summary = tr.reduce(tr.load_xplane(trace_dir), traffic.span_labels())
        log("trace", **{k: v for k, v in trace_summary.items() if k not in ("op_s", "op_calls")})
    traffic.release()
    checks = traffic.check()
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    info = device.describe(devices)
    info["memory_peak_bytes"] = peak
    out = {
        "correct": bool(correct),
        "attempted": int(traffic.attempted),
        "failed": int(traffic.failed),
    }
    if trace:
        view = traffic.view(trace_summary, info)
        metrics = {}
        for m in cell.per_layer():
            value = load_module("metrics", m["name"]).read(view)
            if value is None:
                continue
            if not math.isfinite(value):
                raise ValueError(f"metric {m['name']} read {value}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        info["busy_s"] = trace_summary["busy_s"]
        info["window_s"] = trace_summary["window_s"]
        out["metrics"] = metrics
        out["device"] = info
        out["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": trace_summary["idle_gaps"],
        }
    else:
        values = dict(traffic.end_to_end())
        values["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.benchmark["end_to_end"]}
        metrics = {}
        for n in cell.e2e_names():
            v = values.get(n)
            if v is None or not math.isfinite(v):
                raise ValueError(f"end-to-end metric {n} read {v}")
            metrics[n] = {"value": float(v), "unit": units[n]}
        out["metrics"] = metrics
        out["device"] = info
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    p = argparse.ArgumentParser(description="volcano-tpu benchmark: one run of one cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    except Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result))
    return 0
