"""Device time of the ``tree_select`` Pallas kernel over the traced window,
in percent."""

KERNELS = ("tree_select",)


def read(ctx):
    t = ctx.trace
    if t is None or not any(k in t.kernel_s for k in KERNELS):
        return None
    return 100.0 * sum(t.kernel_s.get(k, 0.0) for k in KERNELS) / t.window_s
