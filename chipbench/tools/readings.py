#!/usr/bin/env python3
"""The readings a limit is set from, in one process (set-up is long): the
numbers compared for the program over many seeds, and for the control - the
plain reference computed in ``control_precision``, the precision below the
one the configuration states, put in the program's place - on a few.

    python3 chipbench/tools/readings.py --workload <cell> --seeds 12 --control 3

Each seed drives the cell's own driver with a short window, so the readings
come from the compiled step, the batch and the feed that the cell times.
The last lines give the largest sound reading and the smallest control
reading of each number; a limit goes between them (PERF.md, section 2).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import compare, run as run_lib, weights  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2147483000)
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)

    import jax

    rehearsal = os.environ.get("CHIPBENCH_REHEARSAL")
    sound, control = [], []
    for i in range(args.seeds):
        seed = args.first_seed + 1009 * i
        ctx = run_lib.context(args.workload, seed, args.seconds, 0, rehearsal)
        driver = run_lib.load_module(ctx["search"], "drivers",
                                     ctx["traffic"]["driver"])
        if i == 0:
            driver.place_cache(jax)
        result, extra = driver.measure(ctx, None)
        row = {r["number"]: r["value"] for r in result["compared"]}
        sound.append(row)
        print(json.dumps({"row": "sound", "seed": seed, **row,
                          "failed": result["failed"]}), flush=True)
        if i < args.control:
            config = ctx["config"]
            reference = run_lib.load_module(ctx["search"], "references",
                                            config["reference"])
            with jax.default_device(jax.devices()[0]):
                params = jax.jit(lambda k: weights.make_flat(
                    extra["shapes"], config["init"], k))(extra["key"])
                precision = config["control_precision"]
                low = reference.run(config, params, extra["batches"],
                                    precision=precision)
            numbers = compare.readings(low, extra["reference"])
            row = {n: numbers[n] for n in compare.NUMBERS}
            control.append(row)
            print(json.dumps({"row": "control", "seed": seed,
                              "precision": precision,
                              **row, "at": numbers["grad_leaf_at"]}),
                  flush=True)
    for name in compare.NUMBERS:
        print(json.dumps({
            "row": "summary", "number": name,
            "sound_max": max(r[name] for r in sound),
            "sound_all": sorted(r[name] for r in sound),
            "control_min": min((r[name] for r in control), default=None),
            "control_all": sorted(r[name] for r in control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
