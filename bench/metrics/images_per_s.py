"""Images answered in the window over the window's length (host clock).

The closed loop's window ends with the first answer at or after
``--seconds``, so the rate covers all the work and all the time."""


def read(ctx):
    win = ctx.window
    done = sum(r.images for r in win.requests if r.done is not None)
    return done / win.length_s
