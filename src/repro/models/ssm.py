"""Mamba-2 (SSD — state-space duality) blocks: chunked scan + O(1) decode.

The chunked formulation (Dao & Gu, arXiv:2405.21060) splits the sequence into
chunks of length ``Q``: a quadratic attention-like *intra-chunk* term (MXU
friendly) and a sequential *inter-chunk* state pass (tiny).  This jnp
implementation is the oracle for the ``repro.kernels.ssd_scan`` Pallas kernel
and the path compiled by the dry-run.

Decode keeps a constant-size recurrent state — the reason the ``long_500k``
cell is runnable for SSM/hybrid architectures only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_ssm_block(key, cfg, dtype):
    d, di, n, h, kk = (
        cfg.d_model,
        cfg.d_inner,
        cfg.ssm_state,
        cfg.ssm_heads,
        cfg.conv_kernel,
    )
    ks = jax.random.split(key, 8)
    std = 0.02
    return {
        "in_x": (jax.random.normal(ks[0], (d, di)) * std).astype(dtype),
        "in_z": (jax.random.normal(ks[1], (d, di)) * std).astype(dtype),
        "in_B": (jax.random.normal(ks[2], (d, n)) * std).astype(dtype),
        "in_C": (jax.random.normal(ks[3], (d, n)) * std).astype(dtype),
        "in_dt": (jax.random.normal(ks[4], (d, h)) * std).astype(dtype),
        "conv_x": (jax.random.normal(ks[5], (cfg.conv_kernel, di)) * std).astype(dtype),
        "conv_B": (jax.random.normal(ks[6], (cfg.conv_kernel, n)) * std).astype(dtype),
        "conv_C": (jax.random.normal(ks[7], (cfg.conv_kernel, n)) * std).astype(dtype),
        "A_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out": (jax.random.normal(jax.random.fold_in(key, 9), (di, d)) * std).astype(dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv along S.  x: [B, S, C]; w: [K, C]."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + pad[:, i : i + x.shape[1], :] * w[i]
    return out


def _conv_step(window: jax.Array, x_t: jax.Array, w: jax.Array):
    """One causal-conv step.  window: [B, K-1, C] (previous inputs)."""
    full = jnp.concatenate([window, x_t[:, None, :]], axis=1)    # [B, K, C]
    out = jnp.einsum("bkc,kc->bc", full, w)
    return out, full[:, 1:, :]


def ssd_chunked(
    xdt: jax.Array,    # [B, S, H, P]   (x pre-multiplied by dt)
    dA: jax.Array,     # [B, S, H]      (dt * A, negative)
    Bmat: jax.Array,   # [B, S, N]
    Cmat: jax.Array,   # [B, S, N]
    chunk: int,
    h0: jax.Array | None = None,   # [B, H, P, N] initial state
):
    """Chunked SSD scan; returns (y [B,S,H,P], h_final [B,H,P,N])."""
    b, s, h, p = xdt.shape
    n = Bmat.shape[-1]
    q = min(chunk, s)
    s_orig = s
    if s % q != 0:
        # Pad with dt=0 tokens: decay exp(0)=1 and zero state contribution,
        # so the final state is exact and padded outputs are discarded.
        pad = q - s % q
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // q

    xdt = xdt.astype(jnp.float32).reshape(b, nc, q, h, p)
    dA = dA.astype(jnp.float32).reshape(b, nc, q, h)
    Bc = Bmat.astype(jnp.float32).reshape(b, nc, q, n)
    Cc = Cmat.astype(jnp.float32).reshape(b, nc, q, n)

    cum = jnp.cumsum(dA, axis=2)                                  # [B,nc,Q,H]
    total = cum[:, :, -1, :]                                      # [B,nc,H]

    # ---- intra-chunk quadratic term -------------------------------------
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)                    # [B,nc,Q,Q]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]           # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((q, q), jnp.bool_))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = cb[..., None] * decay                                # [B,nc,Q,Q,H]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt)

    # ---- inter-chunk state pass ------------------------------------------
    # State contribution of each chunk (decayed to chunk end):
    w_end = jnp.exp(total[:, :, None, :] - cum)                   # [B,nc,Q,H]
    s_chunk = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", w_end, Bc, xdt)

    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    def body(carry, xs):
        h_prev = carry
        s_c, tot_c = xs                                           # [B,H,P,N], [B,H]
        h_new = h_prev * jnp.exp(tot_c)[:, :, None, None] + s_c
        return h_new, h_prev

    (h_final, h_prevs) = jax.lax.scan(
        body,
        h0,
        (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(total, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                         # [B,nc,H,P,N]

    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", Cc, h_prevs) * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(b, s, h, p)[:, :s_orig]
    return y, h_final


def ssm_block(p, cfg, u, *, cache=None, return_cache: bool = False):
    """Mamba-2 block.  u: [B, S, d] → (out, new_cache).

    ``cache``: dict(conv [B, K-1, di+2N], state [B, H, P, N]) for decode;
    ``S == 1`` uses the O(1) recurrence.  ``return_cache`` makes the chunked
    (prefill) path emit the decode cache.
    """
    b, s, d = u.shape
    di, n, h, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    kk = cfg.conv_kernel

    x = u @ p["in_x"]
    z = u @ p["in_z"]
    Bm = u @ p["in_B"]
    Cm = u @ p["in_C"]
    dt = jax.nn.softplus(
        (u @ p["in_dt"]).astype(jnp.float32) + p["dt_bias"]
    )                                                              # [B,S,H]
    A = -jnp.exp(p["A_log"])                                       # [H]

    if cache is None or s > 1:
        if cache is not None:
            raise NotImplementedError("chunked prefill with cache not needed")
        raw_window = jnp.concatenate([x, Bm, Cm], axis=-1)[:, s - (kk - 1):, :]
        x = jax.nn.silu(_causal_conv(x, p["conv_x"]))
        Bm = jax.nn.silu(_causal_conv(Bm, p["conv_B"]))
        Cm = jax.nn.silu(_causal_conv(Cm, p["conv_C"]))
        xh = x.reshape(b, s, h, pdim)
        xdt = xh * dt[..., None]
        dA = dt * A
        if cfg.attn_impl == "pallas" and not return_cache:
            # TPU kernel path (kernels/ssd_scan); the cache-producing prefill
            # needs h_final, which the fused kernel keeps in VMEM — fall back.
            from ..kernels.ssd_scan.ops import ssd_scan as _ssd_kernel

            q = min(cfg.ssd_chunk, s)
            while s % q:
                q //= 2
            y = _ssd_kernel(xdt, dA, Bm, Cm, chunk=q)
            h_final = None
        else:
            y, h_final = ssd_chunked(xdt, dA, Bm, Cm, cfg.ssd_chunk)
        new_cache = (
            {"conv": raw_window, "state": h_final} if return_cache else None
        )
    else:
        # O(1) decode step.
        conv_win = cache["conv"]                                   # [B,K-1,di+2N]
        packed = jnp.concatenate([x[:, 0], Bm[:, 0], Cm[:, 0]], axis=-1)
        w_packed = jnp.concatenate([p["conv_x"], p["conv_B"], p["conv_C"]], axis=1)
        conv_out, conv_win = _conv_step(conv_win, packed, w_packed)
        conv_out = jax.nn.silu(conv_out)
        x_t = conv_out[:, :di].reshape(b, h, pdim).astype(jnp.float32)
        B_t = conv_out[:, di : di + n].astype(jnp.float32)
        C_t = conv_out[:, di + n :].astype(jnp.float32)
        dt_t = dt[:, 0]                                            # [B,H]
        dA_t = jnp.exp(dt_t * A)                                   # [B,H]
        hst = cache["state"]                                       # [B,H,P,N]
        hst = hst * dA_t[:, :, None, None] + (
            (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :]
        )
        y = jnp.einsum("bhpn,bn->bhp", hst, C_t)
        y = y.reshape(b, 1, h, pdim)
        xh = x_t.reshape(b, 1, h, pdim)
        new_cache = {"conv": conv_win, "state": hst}

    y = y + p["D"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(b, s, di)

    # Gated RMSNorm (Mamba-2) then output projection.
    from .layers import rms_norm

    y = y.astype(u.dtype) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], cfg.rms_eps)
    return y @ p["out"], new_cache


def init_ssm_cache(cfg, batch: int, dtype=jnp.float32):
    di, n, h, pdim = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    return {
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, di + 2 * n), dtype),
        "state": jnp.zeros((batch, h, pdim, n), jnp.float32),
    }


def ssd_sequential_ref(xdt, dA, Bmat, Cmat, h0=None):
    """O(S) sequential reference recurrence (oracle for ssd_chunked)."""
    b, s, h, p = xdt.shape
    n = Bmat.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    def body(carry, xs):
        hst = carry
        x_t, dA_t, B_t, C_t = xs
        hst = hst * jnp.exp(dA_t)[:, :, None, None] + (
            x_t[..., None] * B_t[:, None, None, :]
        )
        y_t = jnp.einsum("bhpn,bn->bhp", hst, C_t)
        return hst, y_t

    xs = (
        jnp.moveaxis(xdt.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dA.astype(jnp.float32), 1, 0),
        jnp.moveaxis(Bmat.astype(jnp.float32), 1, 0),
        jnp.moveaxis(Cmat.astype(jnp.float32), 1, 0),
    )
    h_final, ys = jax.lax.scan(body, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h_final
