#!/usr/bin/env python3
"""The benchmark's command: one cell, once, from a new process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A dispatcher and nothing more. It finds the cell in ``BENCHMARK.json``, the
configuration in ``configs/<config>.json``, the traffic in
``traffic/<traffic>.json``, and hands them to the driver the traffic names
(``drivers/<driver>.py``). The driver finds the plain reference
(``references/<name>.py``) and each per-layer metric's reader
(``layer_metrics/<name>.py``) by name too. Nothing here or in a driver knows a
cell or a configuration: a later PR adds files and entries and edits nothing.

``CHIPBENCH_REHEARSAL=<dir>`` adds a directory that is searched for the same
kinds of file (and for ``workloads/<cell>.json``, a cell the manifest does
not list) and lets the command run off the chip: the last line then carries no
metric and ``correct: false``. The driver never sets it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def find(search, kind, name, ext):
    for base in search:
        path = os.path.join(base, kind, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {search}")


def load_json(search, kind, name):
    with open(find(search, kind, name, ".json")) as fh:
        return json.load(fh)


def load_module(search, kind, name):
    path = find(search, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def context(workload, seed, seconds, trace, rehearsal=None):
    """Everything a driver is given."""
    search = [HERE] + ([os.path.abspath(rehearsal)] if rehearsal else [])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cell = next((w for w in manifest["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        if not rehearsal:
            raise SystemExit(f"BENCHMARK.json has no workload {workload!r}")
        cell = load_json(search[1:], "workloads", workload)

    def reported(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "root": ROOT, "here": HERE, "search": search, "cell": cell,
        "config": load_json(search, "configs", cell["config"]),
        "traffic": load_json(search, "traffic", cell["traffic"]),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "rehearsal": bool(rehearsal), "t_start": T_START,
        "end_to_end": [m["name"] for m in manifest["end_to_end"]
                       if reported(m)],
        "per_layer": [m["name"] for m in manifest["per_layer"]
                      if reported(m)],
        "units": {m["name"]: m["unit"] for m in
                  manifest["end_to_end"] + manifest["per_layer"]},
        "load_module": load_module,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    ctx = context(args.workload, args.seed, args.seconds, args.trace,
                  os.environ.get("CHIPBENCH_REHEARSAL"))
    driver = load_module(ctx["search"], "drivers", ctx["traffic"]["driver"])
    result = driver.run(ctx)
    if result is None:  # no chip: no result
        return 1
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
