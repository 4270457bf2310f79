"""The whole step's share of the chip's int8 peak, in the NID batch cell."""

from bench.roofline.share import step_mfu as read  # noqa: F401
