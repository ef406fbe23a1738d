"""Mean wait of a request answered in the window from the tick its tree
settled to the host holding its answer, in milliseconds
(``ServeStats.answer_wait_us / completed``): the rest of the segment in
which it settled, and the fetch.  The settle tick is mapped to the host
clock over its segment (``SearchService.timeline``)."""


def read(ctx):
    total, done = ctx.stats.get("answer_wait_us"), ctx.stats["completed"]
    return total / done / 1e3 if total is not None and done else None
