"""The collection kernel's share of its roofline: the least time of the
work counted from shapes (``work.collection_kernel``) over the kernel's
device time, per execution. The kernel is found by name."""

KERNEL = ("_collection_kernel",)


def read(ctx):
    tr, c = ctx["trace"], ctx["config"]
    t = tr.kernel_seconds(ctx["kernels"], KERNEL)
    runs = tr.kernel_executions(ctx["kernels"], KERNEL)
    if t <= 0 or runs == 0:
        return None
    flops, nbytes = ctx["work"].collection_kernel(
        ctx["counts"]["slices"], c["n_cu"], c["n_ec"])
    least, _ = ctx["work"].roofline_seconds(flops * runs, nbytes * runs, ctx["peak"])
    return 100.0 * least / t
