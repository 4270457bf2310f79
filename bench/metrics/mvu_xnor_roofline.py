"""Share of the dense xnor/popcount kernel's roofline (device trace)."""

from bench.roofline.share import kernel_share


def read(ctx):
    return kernel_share(ctx, "mvu_xnor")
