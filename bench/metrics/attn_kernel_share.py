"""Device time of the attention kernels (``decode_attention``,
``paged_decode_attention``, ``tree_decode_attention``,
``paged_tree_decode_attention``, ``flash_attention``) over the traced
window, in percent."""

KERNELS = (
    "decode_attention", "paged_decode_attention", "tree_decode_attention",
    "paged_tree_decode_attention", "flash_attention",
)


def read(ctx):
    t = ctx.trace
    if t is None or not any(k in t.kernel_s for k in KERNELS):
        return None
    return 100.0 * sum(t.kernel_s.get(k, 0.0) for k in KERNELS) / t.window_s
