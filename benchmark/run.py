#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from
`BENCHMARK.json`: the cell's entry gives its configuration
(`benchmark/configs/<config>.json`, the entry's `file`) and its traffic
mix (`benchmark/traffic/<traffic>.json`); the traffic file names its
runner (`benchmark/runners/<runner>.py`) and its generator
(`benchmark/generators/<generator>.py`), the configuration its model
family (`benchmark/models/<family>.py`) and its plain reference
(`benchmark/reference/<name>.py`); and for `--trace 1` each per-layer
metric the cell reports is read by `benchmark/layer_metrics/<metric>.py`.
Adding any of these is adding files and entries.

The last line of standard output is the result, one JSON object. A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result line. `--rehearse-on-cpu` runs the same code at the
tiny size in the files' `rehearsal` sections with the kernels
interpreted, to debug the harness before chip time is spent: its output
says `platform: cpu`, its numbers are no metrics, and it is never the
default.
"""

import argparse
import importlib.util
import json
import os
import sys
import time
import types

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def _cell_metrics(entries, cell):
    """The metrics a cell reports: those with no `workloads` key, and
    those that list the cell."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def _load_reader(metric):
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="NOT a chip run: tiny sizes, JAX_PLATFORMS=cpu, "
                         "kernels interpreted, for debugging the harness")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = _by_name(bench["workloads"], args.workload, "workload")
    cfg_entry = _by_name(bench["configs"], cell["config"], "config")
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_FORCE_FLASH"] = "1"
        over = config.get("rehearsal", {})
        config = {**config, **over}
        print("benchmark: REHEARSAL on cpu — not a chip run, its numbers "
              "are no metrics", flush=True)
    # the deployment's JAX settings, which JAX reads as it is imported
    os.environ.update(config.get("jax_env", {}))
    sys.path.insert(0, ROOT)

    from benchmark import harness, peaks
    params = harness.load_traffic(cell["traffic"], rehearsal)
    runner = harness.by_name("runners", params["runner"])
    try:
        devices, _cache = harness.claim_devices(int(cell["chips"]),
                                                rehearsal, T_START)
    except harness.NoAccelerator as exc:
        print(f"benchmark: {exc}", file=sys.stderr, flush=True)
        return 1
    import jax
    jax_devices = jax.devices()
    kind = devices[0].device_kind
    ctx = types.SimpleNamespace(
        cell=cell["name"], config=config, traffic=params,
        chips=int(cell["chips"]), seed=args.seed,
        seconds=float(args.seconds if args.seconds is not None
                      else bench["run_seconds"]),
        trace=bool(args.trace), rehearsal=rehearsal, t_start=T_START,
        devices=devices, warm_timeout_s=900.0,
        peaks=None if rehearsal else peaks.peaks_for(kind))
    run = runner.run(ctx)

    if args.trace:
        metrics = {}
        for m in _cell_metrics(bench["per_layer"], ctx.cell):
            value = _load_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run.e2e[m["name"]],
                               "unit": m["unit"]}
                   for m in _cell_metrics(bench["end_to_end"], ctx.cell)
                   if m["name"] in run.e2e}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax_devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace and run.traced is not None \
            and run.traced.device is not None:
        device["busy_s"] = run.traced.device.busy_s()
        device["window_s"] = run.traced.device.window_s()
        result["breakdown"] = run.traced.device.breakdown()
    result["facts"] = {k: v for k, v in run.facts.items()
                       if isinstance(v, (int, float, str, type(None)))}
    result["facts"]["wall_s"] = time.perf_counter() - T_START
    if rehearsal:
        result["rehearsal"] = True
    # every number `correct` compared, beside its limit: last in the
    # line, and the last lines on standard error
    result["compared"] = run.compared
    print(json.dumps(result), flush=True)
    for name, pair in run.compared.items():
        print(f"compared {name} {pair['value']:.6g} limit "
              f"{pair['limit']:.6g}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
