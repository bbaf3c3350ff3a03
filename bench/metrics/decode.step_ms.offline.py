"""Model step: device time per execution of the decode program (the
program that holds the paged attention kernel), in ms."""


def read(ctx):
    execs = ctx.executions(ctx.decode_kernel())
    if not execs:
        return None
    return sum(m[2] for m, _ in execs) / len(execs) * 1e-6
