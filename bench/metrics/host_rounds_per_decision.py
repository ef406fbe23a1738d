"""Host rounds of the serving loop per decision completed in the window
(``ServeStats.host_rounds / completed``): one round is one ``poll``, one
``serve_segment`` dispatch and one fetch of its completions."""


def read(ctx):
    done = ctx.stats["completed"]
    return ctx.stats["host_rounds"] / done if done else None
