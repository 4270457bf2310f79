"""Share of the integer MVU kernel's roofline (device trace)."""

from bench.roofline.share import kernel_share


def read(ctx):
    return kernel_share(ctx, "mvu_int")
