"""Process start to the first timed call: device init, the native
library, the inputs, and every compile or cache load of the warm-up."""


def read(ctx):
    return ctx.setup_s
