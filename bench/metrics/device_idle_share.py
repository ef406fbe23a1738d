"""One minus the union of device op intervals over the traced window, in
percent, averaged over the chips used."""


def read(ctx):
    t = ctx.trace
    return None if t is None else 100.0 * (1.0 - t.busy_s / t.window_s)
