"""Process start to the first timed request (host clock): device
start-up, weights, images, planning, and compile or cache load."""


def read(ctx):
    return ctx.setup["setup_s"]
