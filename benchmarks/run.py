"""Benchmark harness: one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV. Sections:
  fig5/fig6   skew-aware mechanism ablations (collection / training evenness)
  fig7        trained-model accuracy under scheduling ablations
  fig8        DS vs Learning-aid DS across step sizes (cost/backlog/skew)
  fig9        unit framework cost vs baselines across N / M (headline: cost
              reduction vs CUFull)
  sched_scale scheduler wall-time scaling + matching kernel
  fleet_scale K-slice fleet engine scaling (BENCH JSON rows)
  ragged_scale padded mixed-shape fleet vs per-shape sub-fleets (BENCH rows)
  policy_scale mixed-policy switch-dispatch fleet vs per-spec sub-fleets
              (wall-clock per slot + compile counts vs K and n_specs)
  matching_scale kernel-vs-reference cost of the three greedy matchers
              across N x M (BENCH rows; Pallas timings on TPU)
  roofline    aggregated dry-run roofline terms (run scripts/dryrun_sweep.sh
              first; missing artifacts are skipped gracefully)

Every BENCH row printed to stdout is also written to a ``BENCH_<name>.json``
artifact at the end of the run (common.write_bench_artifacts), so the perf
trajectory survives the CI log; the weekly workflow uploads them.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    print("name,us_per_call,derived")
    from . import (common, fig7_accuracy, fleet_scale, matching_scale,
                   paper_figs, policy_scale, ragged_scale, roofline,
                   sched_scale)

    sections = [
        ("fig5", paper_figs.fig5_collection_evenness),
        ("fig6", paper_figs.fig6_training_evenness),
        ("fig7", fig7_accuracy.fig7_accuracy),
        ("fig8", paper_figs.fig8_ds_vs_lds),
        ("fig9", paper_figs.fig9_unit_cost),
        ("sched_scale", sched_scale.sched_scale),
        ("fleet_scale", fleet_scale.fleet_scale),
        ("ragged_scale", ragged_scale.ragged_scale),
        ("policy_scale", policy_scale.policy_scale),
        ("matching_scale", matching_scale.matching_scale),
        ("matching", sched_scale.matching_kernel_bench),
        ("roofline", roofline.roofline_table),
    ]
    failures = 0
    for name, fn in sections:
        try:
            fn()
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    for path in common.write_bench_artifacts():
        print(f"artifact/{path},0,written")
    print(f"summary/sections_failed,0,{failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
