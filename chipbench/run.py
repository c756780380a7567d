#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix; the configuration's sizes are in
``chipbench/configs/<config>.json``, the mix's parameters in
``chipbench/traffic/<mix>.json``, the loop that drives the program in
``chipbench/traffic/<kind>.py`` (the mix names its kind), the limits of
the correctness comparison in ``chipbench/limits/<cell>.json``, and each
per-layer metric's reader in ``chipbench/metrics/<metric>.py`` (or, for
``<base>.<cells>``, in ``<base>.py``).

A run: checks that JAX sees a TPU whose ``device_kind`` is in
``peaks.json`` and as many chips as the cell asks for, else exits
non-zero and prints no result; generates the cell's data from ``--seed``;
sets up and warms every shape the window uses (``setup_s``); measures
for ``--seconds`` (with ``--trace 1`` under the profiler, reporting the
per-layer metrics instead of the end-to-end ones); reads the device's
peak memory; then compares what the window produced with the plain host
reference and prints each number beside its limit, last on standard
error, and the result as the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(HERE, "traffic"), HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import peaks as peaklib  # noqa: E402
import reference  # noqa: E402


class NoResult(Exception):
    """The run cannot measure: exit non-zero and print no result."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# loaded by path: a module named ``trace`` would shadow the standard one
tracelib = _module(os.path.join(HERE, "trace.py"), "chipbench_trace")


def cell_spec(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, resolved by name from BENCHMARK.json."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NoResult(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = os.path.join(root, "chipbench")
    mix = _json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return {
        "name": name,
        "chips": w["chips"],
        "config": _json(os.path.join(root, config["file"])),
        "mix": mix,
        "kind_file": os.path.join(here, "traffic", mix["kind"] + ".py"),
        "limits": _json(os.path.join(here, "limits", name + ".json")),
        "end_to_end": e2e,
        "per_layer": per_layer,
        "run_seconds": bench["run_seconds"],
    }


def reader(metric: str, root: str = ROOT):
    """The per-layer metric's reader module: metrics/<name>.py, else
    metrics/<base>.py for a name <base>.<cells>."""
    here = os.path.join(root, "chipbench", "metrics")
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(here, stem + ".py")
        if os.path.exists(path):
            return _module(path, "metric_" + stem.replace(".", "_"))
    raise NoResult(f"no reader for per-layer metric {metric!r} in {here}")


def preflight(chips: int) -> tuple[list, dict]:
    """(devices, peaks) of the TPU this run measures, or NoResult."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise NoResult(f"no src/repro beside {os.path.basename(HERE)}/: "
                       f"the system under test is missing")
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        raise NoResult("REPRO_PALLAS_INTERPRET is set: the kernels would "
                       "run in interpret mode, not on the chip")
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoResult(f"no TPU: jax.default_backend() is {backend!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoResult(f"the cell needs {chips} TPU chips, JAX finds "
                       f"{len(devices)}")
    try:
        peak = peaklib.peaks(devices[0].device_kind)
    except KeyError as e:
        raise NoResult(str(e)) from None
    return devices[:chips], peak


def compile_cache(root: str = ROOT) -> None:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at the fixed <checkout>/.jax_cache; every program is kept,
    so that a cell's second run in a checkout compiles nothing."""
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices: list, peak: dict, t_start: float = T_START) -> dict:
    """Set up, measure, check; return the result line's object."""
    import jax
    kind = _module(spec["kind_file"], "kind_" + spec["mix"]["kind"])
    span = jax.profiler.TraceAnnotation
    cell = kind.Cell(spec["config"], spec["mix"], seed, span)
    with span("setup"):
        cell.setup()
    setup_s = time.perf_counter() - t_start
    phases = {"setup_s": setup_s}
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        if trace:
            tracelib.start(tdir)
        t0 = time.perf_counter()
        with span(tracelib.WINDOW_SPAN):
            win = cell.window(seconds)
        phases["window_s"] = time.perf_counter() - t0
        reduced = None
        if trace:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            phases["trace_stop_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            events = tracelib.load(tracelib.xplane_file(tdir))
            phases["trace_load_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            reduced = tracelib.reduce(events)
            phases["trace_reduce_s"] = time.perf_counter() - t0
            del events
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    t0 = time.perf_counter()
    cell.release()
    numbers = cell.compare()
    phases["compare_s"] = time.perf_counter() - t0
    correct, table = reference.verdict(numbers, spec["limits"])

    if trace:
        records = dict(win["records"], trace=reduced, peak=peak)
        metrics, absent = {}, []
        for m in spec["per_layer"]:
            v = reader(m["name"]).read(m["name"], records)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            else:
                absent.append(m["name"])
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    # logged on standard error by main, not printed in the result line
    result["readings"] = {k: v for k, v in numbers.items() if k not in table}
    result["absent"] = absent if trace else []
    result["records"] = {k: v for k, v in win["records"].items()
                         if isinstance(v, (int, float, list))}
    result["phases"] = phases
    result["checks"] = table           # the result line's last key
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = cell_spec(args.workload)
        devices, peak = preflight(spec["chips"])
    except (NoResult, OSError, KeyError) as e:
        print(f"chipbench: no result: {e}", file=sys.stderr, flush=True)
        return 2
    compile_cache()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, peak)
    print("records " + json.dumps(result.pop("records")), file=sys.stderr)
    print("phases " + json.dumps(result.pop("phases")), file=sys.stderr)
    for name in result.pop("absent"):
        print(f"absent {name}: its reader found nothing to read",
              file=sys.stderr)
    for name, value in result.pop("readings").items():
        print(f"reading {name} {value!r} (not compared)", file=sys.stderr)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
