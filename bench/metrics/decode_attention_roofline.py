"""Share of its roofline the ``decode_attention`` kernel reached over the
traced window, in percent.

The kernel's least time is the key and value bytes its decode steps have to
read over the chip's HBM bandwidth (``bench/peaks.json``): every slot of
every tick reads each attended position's keys and values once per layer,
``ServeStats.attended_positions x layers x 2 x kv_heads x head_dim`` elements
in the model's dtype.  Bytes bound it: one query token per slot does a few
FLOPs per element read, far below the chip's FLOPs per byte.  The time is
the kernel's device self time in the trace.
"""

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
KERNEL = "decode_attention"


def read(ctx):
    t = ctx.trace
    positions = ctx.stats.get("attended_positions")
    hbm = ctx.peak.get("hbm_bytes_per_s")
    if t is None or not positions or not hbm or not t.kernel_s.get(KERNEL):
        return None
    c = ctx.cell["config"]
    need = (positions * c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * DTYPE_BYTES[c["torch_dtype"]])
    return 100.0 * need / (hbm * t.kernel_s[KERNEL])
