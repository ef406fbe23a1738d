"""Mean wait of a request answered in the window from its submission to its
admission into a tree row, in milliseconds (``ServeStats.queue_wait_us /
completed``): the host queue and the device ring together.  Admission is a
device tick mapped to the host clock over its segment (``SearchService.
timeline``)."""


def read(ctx):
    total, done = ctx.stats.get("queue_wait_us"), ctx.stats["completed"]
    return total / done / 1e3 if total is not None and done else None
