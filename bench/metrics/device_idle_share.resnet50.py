"""Share of the traced window in which no operation ran on the device, in
the ResNet-50 cell."""

from bench.harness.trace import idle_share as read  # noqa: F401
