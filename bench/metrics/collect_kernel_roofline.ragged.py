"""The collection kernel's share of its roofline in a ragged fleet: the
least time of the work of every slice at its true shape (the sum over
slices of ``work.collection_kernel(1, N_k, M_k)``) over the kernel's device
time, per execution. Padded links are not counted as work, so a kernel that
skips them reads higher. A configuration without ``slices`` gives None."""

KERNEL = ("_collection_kernel",)


def read(ctx):
    tr, slices = ctx["trace"], ctx["config"].get("slices")
    if not slices:
        return None
    t = tr.kernel_seconds(ctx["kernels"], KERNEL)
    runs = tr.kernel_executions(ctx["kernels"], KERNEL)
    if t <= 0 or runs == 0:
        return None
    work = [ctx["work"].collection_kernel(1, s["n_cu"], s["n_ec"]) for s in slices]
    flops, nbytes = sum(f for f, _ in work), sum(b for _, b in work)
    least, _ = ctx["work"].roofline_seconds(flops * runs, nbytes * runs, ctx["peak"])
    return 100.0 * least / t
