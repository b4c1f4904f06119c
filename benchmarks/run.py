"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by the name ``BENCHMARK.json`` gives it:

- ``benchmarks/workloads/<cell>.json``: the runner (``train_job`` or
  ``serve_open_loop``, a module of ``benchmarks/runners``) and the job's or
  the traffic mix's parameters;
- the configuration's ``file``: the model's ``family`` and sizes, how it is
  trained and served, and the names of its plain reference
  (``benchmarks/references/<name>.py``) and of its FLOP count
  (``benchmarks/costs/<name>.py``);
- ``benchmarks/layer_metrics/<metric>.py``: ``read(trace, facts)`` returns
  the number, or ``None`` when there is nothing to read.

A new cell, configuration or per-layer metric is new files plus new entries
in ``BENCHMARK.json``; nothing here is edited. The last line of standard
output is the result; earlier lines carry sample counts and unjudged tails.
"""
from __future__ import annotations

import time

STARTED = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def load_cell(name: str, root: str = CHECKOUT) -> tuple:
    """(cell entry, cell file, configuration file, per-layer and end-to-end
    metric entries of this cell) from ``<root>/BENCHMARK.json`` and the
    files it names. ``root`` is the checkout; the rehearsal points it at a
    toy benchmark under ``benchmarks/tests/data``. The configuration states
    its ``family`` and names its ``reference`` and ``costs``: ``files`` holds
    where each was found."""
    from benchmarks.lib import named

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    entry = cells[name]
    config_entry = next(
        c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, bench["paths"][0], "workloads",
                           name + ".json")) as f:
        cell = dict(json.load(f), name=name)
    with open(os.path.join(root, config_entry["file"])) as f:
        config = dict(json.load(f), name=config_entry["name"])
    named.need(config, "family", config_entry["file"])
    config["files"] = {
        key: named.find(key, named.need(config, key, config_entry["file"]),
                        os.path.join(root, bench["paths"][0]),
                        config_entry["file"])
        for key in named.KINDS}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return entry, cell, config, per_layer, end_to_end


def read_layer_metric(name: str, trace, facts: dict):
    from benchmarks.lib import named

    return named.load(os.path.join(
        BENCH_DIR, "layer_metrics", name + ".py")).read(trace, facts)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", root: str = CHECKOUT) -> dict:
    """The result object. ``platform`` is ``tpu`` for every real run; the
    rehearsal under ``benchmarks/tests`` passes ``cpu`` to drive the same
    code at toy size, and its numbers are never device numbers."""
    from benchmarks.lib import cluster

    entry, cell, config, per_layer, end_to_end = load_cell(workload, root)
    chips = int(entry["chips"])
    cluster.prepare_environment()
    if platform == "tpu":
        found = cluster.chips_on_this_machine()
        if found < chips:
            raise cluster.NoAccelerator(
                f"{workload} needs {chips} TPU chip(s), this machine "
                f"exposes {found}")
    runner = importlib.import_module("benchmarks.runners." + cell["runner"])
    got = runner.run(cell, config, seed=seed, seconds=seconds, trace=trace,
                     platform=platform, chips=chips, started=STARTED)
    device = got["device"]
    result = {"correct": got["correct"], "attempted": got["attempted"],
              "failed": got["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in end_to_end:
            result["metrics"][m["name"]] = {
                "value": got["end_to_end"][m["name"]], "unit": m["unit"]}
        return result
    from benchmarks.lib import trace as trace_lib

    tr = trace_lib.load(got["trace_dir"])
    device["busy_s"], device["window_s"] = trace_lib.busy_s(tr), tr.window_s
    if platform == "tpu" and not device["busy_s"] > 0:
        raise RuntimeError("the trace holds no device operation")
    for m in per_layer:
        value = read_layer_metric(m["name"], tr, got["facts"])
        if value is not None:
            result["metrics"][m["name"]] = {
                "value": float(value), "unit": m["unit"]}
    result["breakdown"] = trace_lib.breakdown(tr)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    from benchmarks.lib.cluster import NoAccelerator

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
