"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file found by its name in ``BENCHMARK.json``:

    benchmarks/workloads/<cell>.json       driver kind, check limits
    benchmarks/configs/<config>.json       sizes as run, source, reductions
    benchmarks/traffic/<traffic>.json      parameters of the generator
    benchmarks/drivers/<kind>.py           run(...) and check(...)
    benchmarks/reference/<name>.py         the configuration's plain reference
    benchmarks/layer_metrics/<metric>.py   read(ctx) -> value or None

The last line of standard output is the result.  Without the chips the
cell asks for, the exit code is not 0 and there is no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()          # process start, for setup_s

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, base: str = HERE):
    """-> (cell, config, traffic) from the files the name leads to."""
    cell = _load_json(base, "workloads", name + ".json")
    config = _load_json(base, "configs", cell["config"] + ".json")
    traffic = _load_json(base, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metrics_of(benchmark: dict, cell_name: str, group: str) -> list:
    """The metrics of ``group`` that this cell reports: those without a
    ``workloads`` key whose end-to-end metric the cell reports, and
    those that list the cell."""
    e2e = [m for m in benchmark["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if group == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in benchmark["per_layer"] if m["moves"] in reported
            and cell_name in m.get("workloads", [cell_name])]


def _reader(metric_name: str):
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def require_chips(chips: int) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            f"benchmark needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}\n")
        raise SystemExit(3)


def device_facts(memory_peak_bytes: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak_bytes}


def run_cell(cell: dict, config: dict, traffic: dict, benchmark: dict,
             seed: int, seconds: float, trace: bool, work_dir: str,
             t0: float = None) -> dict:
    """Drive one run and return the result object.  Looks for no chip:
    ``main`` does, and the tests drive this on the CPU."""
    from benchmarks.harness import compare, trace_reduce
    from benchmarks.harness.compile_clock import clock

    clock()
    t0 = T0 if t0 is None else t0
    driver = importlib.import_module("benchmarks.drivers." + cell["driver"])
    trace_dir = None
    if trace:
        trace_dir = os.path.join(work_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = driver.run(cell, config, traffic, seed, seconds, trace_dir)
    setup_s = result["t_window_start"] - t0
    device = device_facts(result["memory_peak_bytes"])
    gc.collect()                      # the program's state is freed

    metrics = {}
    breakdown = None
    if not trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in metrics_of(benchmark, cell["name"], "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        reduced = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace_reduce.busy_seconds(reduced)
        device["window_s"] = result["window_s"]
        ctx = {"trace": reduced, "facts": result["facts"], "config": config,
               "traffic": traffic, "device_kind": device["kind"],
               "window_s": result["window_s"], "busy_s": device["busy_s"]}
        for m in metrics_of(benchmark, cell["name"], "per_layer"):
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": trace_reduce.top_ops(reduced),
                     "idle_gaps": trace_reduce.idle_gaps(reduced)}

    sys.stderr.write(json.dumps({
        "setup_s": setup_s, "window_s": result["window_s"],
        "before_window": result["facts"].get("compile_before_window"),
        "facts": {k: v for k, v in result["facts"].items()
                  if not isinstance(v, (list, dict))}}) + "\n")

    numbers = driver.check(cell, config, seed, result)
    correct, compared = compare.judge(numbers, cell["check"]["limits"])
    correct = correct and result["failed"] == 0
    sys.stderr.write(compare.stderr_lines(compared) + "\n")
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                       for k, v in compared.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    benchmark = _load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = load_cell(args.workload)
    # One fixed cache directory inside the checkout (the path is part of
    # the cache's key); the program takes the one the environment names.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    require_chips(cell["chips"])
    import ray_tpu  # noqa: F401  (a checkout without the program fails here)

    work_dir = os.path.join(ROOT, ".bench_work")
    os.makedirs(work_dir, exist_ok=True)
    out = run_cell(cell, config, traffic, benchmark, args.seed, args.seconds,
                   bool(args.trace), work_dir)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
