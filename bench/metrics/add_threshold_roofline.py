"""Share of the add_threshold kernel's roofline (device trace)."""

from bench.roofline.share import kernel_share


def read(ctx):
    return kernel_share(ctx, "add_threshold")
