"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for; without them it exits non-zero and prints no result. With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the same
window. The last line of stdout is the result (JSON); the numbers compared
for ``correct`` are printed last on stderr and last in that line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402

# A traced run records this many seconds of the window at most: the trace
# of a longer one takes minutes to read back.
TRACE_SECONDS = 2.0


def _reader_context(res, counts, run, kernels):
    import work
    return {"trace": res, "counts": counts, "config": run["config"],
            "traffic": run["traffic"], "work": work, "kernels": kernels,
            "peak": work.peaks_for(counts["device_kind"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    run = harness.resolve(harness.load_spec(), args.workload)
    chips = int(run["cell"]["chips"])
    devs = harness.require_tpu(chips)
    harness.enable_cache()
    driver = harness.load_module(run["driver"]).Driver(
        run["config"], run["traffic"], args.seed, chips)

    driver.setup()
    setup_s = time.perf_counter() - T_START
    if args.trace:
        import trace as bench_trace
        seconds = min(args.seconds, TRACE_SECONDS)
        counts, red = bench_trace.capture(lambda: driver.window(seconds))
    else:
        counts, red = driver.window(args.seconds), None
    device = harness.device_info(devs)
    counts["device_kind"] = device["kind"]

    if args.trace:
        metrics = {}
        ctx = _reader_context(red, counts, run,
                              bench_trace.pallas_kernels(driver.program_text()))
        for m in run["per_layer"]:
            value = harness.load_module(run["readers"][m["name"]]).read(ctx)
            if value is None:  # left out of the line, and said so
                sys.stderr.write(f"metric {m['name']}: its reader found "
                                 "nothing to read in this trace\n")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    else:
        metrics = driver.end_to_end(counts)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        names = {m["name"] for m in run["end_to_end"]}
        metrics = {k: v for k, v in metrics.items() if k in names}

    driver.free()
    correct, checks, _ = driver.check()
    # every slot of the window is attempted; one that raised would have
    # ended the run, so none failed
    harness.print_result(correct, counts["slots"], 0, metrics, device, checks,
                         red.breakdown() if red is not None else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
