"""Mean duration of the batcher's ``dispatch`` spans (host time to pad a
batch and enqueue it on a replica), from a tracer handed to the batcher."""


def read(ctx):
    if not ctx.dispatch_s:
        return None
    return 1e3 * sum(ctx.dispatch_s) / len(ctx.dispatch_s)
