"""Microbatch steps the engine's stream plan runs for one call (a count)."""


def read(ctx):
    return None if ctx.plan is None else ctx.plan["n_micro"]
