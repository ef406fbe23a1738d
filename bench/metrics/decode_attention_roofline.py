"""Share of its roofline the ``decode_attention`` kernel reached over the
traced window, in percent.

The kernel's least time is the key and value bytes its decode steps have to
read (the architecture module's ``attention_kv_bytes``, from the window's
``ServeStats``) over the chip's HBM bandwidth (``bench/peaks.json``).  Bytes
bound it: one query token per slot does a few FLOPs per element read, far
below the chip's FLOPs per byte.  The time is the kernel's device self time
in the trace.
"""

KERNEL = "decode_attention"


def read(ctx):
    t = ctx.trace
    need = ctx.arch.attention_kv_bytes(ctx.cell["config"], ctx.stats)
    hbm = ctx.peak.get("hbm_bytes_per_s")
    if t is None or not need or not hbm or not t.kernel_s.get(KERNEL):
        return None
    return 100.0 * need / (hbm * t.kernel_s[KERNEL])
