"""Busiest replica's dispatches over the mean, in the window (1 is even)."""


def read(ctx):
    load = ctx.replica_load
    if not load or len(load) < 2 or not sum(load.values()):
        return None
    return max(load.values()) * len(load) / sum(load.values())
