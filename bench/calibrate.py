"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--set key=json ...]

For each seed, in one process: set up the cell, run a short window at the
cell's own load, and print the numbers its check compares, from the program
(side ``program``) and, for the control seeds, from each other side the
driver offers in the program's place: ``control``, the reference computed
in the precision below the configuration's. One JSON line per reading; the
limits go between the largest program reading and the smallest control or
fault reading.
``--set`` overrides a key of the configuration, to read the program run
otherwise than the configuration states (such runs set no limit).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[],
                    help="seeds on which every other side of the check is read too")
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=JSON")
    args = ap.parse_args(argv)

    run = harness.resolve(harness.load_spec(), args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        run["config"][key] = json.loads(value)
    chips = int(run["cell"]["chips"])
    harness.require_tpu(chips)
    harness.enable_cache()
    mod = harness.load_module(run["driver"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        drv = mod.Driver(run["config"], run["traffic"], seed, chips)
        drv.setup()
        counts = drv.window(args.seconds)
        drv.free()
        sides = drv.SIDES if seed in args.control_seeds else ("program",)
        for side in sides:
            ok, checks, extra = drv.check(side)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "set": args.set,
                              "side": side, "correct": ok,
                              "readings": {n: v for n, v, _ in checks},
                              "window_slots": counts["slots"],
                              "seconds": time.perf_counter() - t0}), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
