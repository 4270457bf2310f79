"""Shares of the chip's peaks: a kernel's roofline from the trace and the
work counts, the whole step's from the window's rate."""

from __future__ import annotations

from bench.roofline import work


def kernel_share(ctx, kernel: str) -> float | None:
    """Percent of the kernel's traced time the chip would need at its
    peaks for the same work.  ``None`` when the trace holds no call of it.

    Each layer that runs ``kernel`` makes one call per microbatch, so the
    traced calls divided by those layers counts whole passes; a pass needs
    at least the sum over its layers of each layer's bound.
    """
    if ctx.trace is None or ctx.plan is None:
        return None
    seconds, calls = ctx.trace.kernel(kernel)
    layers = ctx.family.kernel_calls(ctx.config, ctx.plan["microbatch"]).get(
        kernel, [])
    if not calls or not layers or seconds <= 0:
        return None
    peak = ctx.peaks()
    t_pass = sum(work.least_time(ops, nbytes, peak)[0]
                 for ops, nbytes in layers)
    return 100.0 * (calls / len(layers)) * t_pass / seconds


def step_mfu(ctx) -> float | None:
    """Percent of the chip's int8 peak that the window's samples per
    second make at the algorithm's operations per sample (2 per MAC)."""
    if ctx.rate is None:
        return None
    ops = ctx.family.ops_per_sample(ctx.config)
    return 100.0 * ctx.rate * ops / ctx.peaks()["int8_ops_per_s"]
