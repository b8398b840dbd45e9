"""setup_s: process start to window start -- interpreter, JAX and TPU
start-up, cached compiles of the cell's programs, weights and payload
pools from the seed, and the warm-up stretch (host clock)."""


def read(ctx):
    return ctx.setup_s
