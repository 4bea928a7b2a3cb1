"""Device time of the Pallas matcher kernels per slot of the window: the
skew-aware collection and the EC pairing, found by kernel name."""

KERNELS = ("_collection_kernel", "_pairing_kernel")


def read(ctx):
    t = ctx["trace"].kernel_seconds(ctx["kernels"], KERNELS)
    slots = ctx["counts"]["slots"]
    return None if t <= 0 or slots == 0 else 1e3 * t / slots
