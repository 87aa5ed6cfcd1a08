#!/usr/bin/env python3
"""The benchmark's one entry point:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data. ``BENCHMARK.json`` names
the cell's configuration and traffic mix; the configuration is a file
of sizes (``benchmarks/configs/``) with its plain reference
(``benchmarks/references/``); the traffic mix is a file of parameters
(``benchmarks/traffic/``) that names its runner
(``benchmarks/runners/``); each per-layer metric is a reader of its own
(``benchmarks/layer_metrics/<metric>.py``). This file knows no cell, no
configuration and no metric by name.

The last line of standard output is the result: one JSON object. With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Step times, cache hits, memory and
the checks go on earlier lines (``BENCH {json}``). A run that may not
be measured here (no TPU, too few chips, an unknown chip, a step
without kernels, no program beside the benchmark) prints its reason,
no result, and exits with code 2.
"""

import time

PROCESS_START = time.time()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(**record):
    print("BENCH " + json.dumps(record, default=repr), flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_of(entries, cell_name):
    """The manifest's metrics that this cell reports."""
    return [
        m for m in entries
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def read_layer_metric(name, run):
    """``benchmarks/layer_metrics/<name>.py``'s ``read(run)``; a reader
    that finds nothing to read returns None and the metric is left out.
    One that is listed for its cells alone and finds there nothing of
    what it is for may raise: the run then fails and prints no result."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    manifest = load_json("BENCHMARK.json")
    cell = by_name(manifest["workloads"], args.workload, "workload")
    config_entry = by_name(manifest["configs"], cell["config"], "config")
    config = load_json(config_entry["file"])
    traffic = load_json("benchmarks", "traffic", cell["traffic"] + ".json")

    from benchmarks.lib.device import Refused

    if importlib.util.find_spec("dlrover_tpu") is None:
        print("refused: no program (dlrover_tpu) beside the benchmark")
        return 2
    runner = importlib.import_module(
        "benchmarks.runners." + traffic["runner"]
    )
    ctx = {
        "root": ROOT, "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "process_start": PROCESS_START, "say": say,
    }
    try:
        run = runner.run(ctx)
    except Refused as exc:
        print(f"refused: {exc}")
        return 2

    metrics = {}
    run["say"] = say  # a reader may put what it summed on a BENCH line
    if args.trace:
        for m in metrics_of(manifest["per_layer"], cell["name"]):
            value = read_layer_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(manifest["end_to_end"], cell["name"]):
            metrics[m["name"]] = {
                "value": run["end_to_end"][m["name"]], "unit": m["unit"]
            }
    result = {
        "correct": bool(run["correct"]),
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": metrics,
        "device": run["device"],
    }
    if args.trace and run.get("trace"):
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    # each number compared beside its limit: the last lines of standard
    # error, which is what is kept of a run that is not correct
    for name, ok, value, limit in run["checks"]:
        print(
            f"check {name}: {value!r} against {limit!r}:"
            f" {'ok' if ok else 'NOT OK'}", file=sys.stderr,
        )
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    # the benchmark is imported as the package ``benchmarks`` and the
    # program as ``dlrover_tpu``, both from the checkout's root; python
    # put this file's directory first
    sys.path[0] = ROOT
    sys.exit(main())
