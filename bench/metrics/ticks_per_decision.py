"""Master ticks of the device ring per decision completed in the window
(``ServeStats.ticks / completed``)."""


def read(ctx):
    done = ctx.stats["completed"]
    return ctx.stats["ticks"] / done if done else None
