"""Device time of the slot program outside the Pallas matchers, per slot:
network sampling, the training-allocation solvers and the dynamics."""

PROGRAM = r"_fleet_scan"
KERNELS = ("_collection_kernel", "_pairing_kernel")


def read(ctx):
    tr = ctx["trace"]
    slots = ctx["counts"]["slots"]
    prog = sum(tr.module_runs(PROGRAM))
    if prog <= 0 or slots == 0:
        return None
    return 1e3 * (prog - tr.kernel_seconds(ctx["kernels"], KERNELS)) / slots
