"""Share of tree-row ticks in which a row of the device ring held no live
search (``ServeStats.slot_idle_frac``: ``1 - busy_tree_ticks / (ticks *
batch)``), in percent."""


def read(ctx):
    cap = ctx.stats["ticks"] * ctx.stats["batch"]
    return 100.0 * (1.0 - ctx.stats["busy_tree_ticks"] / cap) if cap else None
