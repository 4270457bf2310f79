"""Share of the xnor line-buffer conv kernel's roofline (device trace)."""

from bench.roofline.share import kernel_share


def read(ctx):
    return kernel_share(ctx, "conv_mvu_xnor")
