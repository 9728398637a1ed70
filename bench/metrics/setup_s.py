"""Set-up: process start to window open (weights, compiles or cache loads,
warm-up, pre-roll), on the host clock."""


def read(ctx):
    return ctx.setup_s
