"""Model FLOPs of the window (the architecture module's ``window_flops``;
for ``bench/archs/dense.py``: decode of every busy row's slots and the
staging prefill at its padded length, 2 FLOPs per matmul parameter per
token, attention left out) over the window's seconds and the chip's bf16
peak, in percent."""


def read(ctx):
    peak = ctx.peak.get("bf16_flops_per_s")
    if not peak or ctx.flops <= 0:
        return None
    return 100.0 * ctx.flops / (ctx.window_s * peak)
