"""Process start to the first timed step: imports, the trainer's own
construction, weights and traffic from the seed, compiling or loading the
step, the three checked steps and the settling steps."""


def read(ctx):
    return ctx.setup_s
