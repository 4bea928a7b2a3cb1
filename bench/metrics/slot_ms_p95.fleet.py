"""95th percentile of the device time of one slot program execution."""
import statistics

PROGRAM = r"_fleet_scan"


def read(ctx):
    runs = ctx["trace"].module_runs(PROGRAM)
    if len(runs) < 20:
        return None
    return 1e3 * statistics.quantiles(runs, n=20)[-1]
