"""Mamba-2 SSD (state-space duality) block — chunked dual form + decode step.

Follows the SSD algorithm of Mamba-2 [arXiv:2405.21060]: the sequence is
split into chunks of ``L``; within-chunk terms use the quadratic (attention
-like) form, cross-chunk information flows through the recurrent state
``(B, H, P, N)`` with a ``lax.scan`` over chunks.  Single-token decode uses
the pure recurrence.  n_groups = 1 (B/C shared across heads).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense, rms_norm
from repro.models.sharding import replicate, shard


class SSMState(NamedTuple):
    ssm: jnp.ndarray        # (B, H, P, N)
    conv: jnp.ndarray       # (B, W-1, conv_dim) rolling conv window


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv; x: (B, S, C), w: (W, C).

    One ``lax.conv`` (not W padded shifts): under sequence sharding GSPMD
    exchanges only the (W-1)-row halo instead of permuting the full tensor
    per shift (§Perf iteration log).
    """
    width, c = w.shape
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w.astype(jnp.float32).reshape(width, 1, c),
        window_strides=(1,), padding=[(width - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """a: (..., L) -> (..., L, L) lower-triangular pairwise cumulative sums."""
    l = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    # sum_{j<k<=i} a_k  = cs[i] - cs[j]
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, chunk: int,
                init_state: Optional[jnp.ndarray] = None):
    """SSD scan.  x: (B, S, H, P); a: (B, S, H) log-decay (dt*A);
    b/c: (B, S, N).  Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = x.shape[1] // chunk
    xc = x.reshape(bsz, nc, chunk, h, p)
    ac = a.reshape(bsz, nc, chunk, h).transpose(0, 1, 3, 2)   # (B,nc,H,L)
    bc = b.reshape(bsz, nc, chunk, n)
    cc = c.reshape(bsz, nc, chunk, n)

    a_cs = jnp.cumsum(ac, axis=-1)                            # (B,nc,H,L)
    # --- intra-chunk (quadratic) term ---
    lmat = jnp.exp(_segsum(ac))                               # (B,nc,H,L,L)
    lmat = shard(lmat, "bchll")
    scores = jnp.einsum("bcln,bcsn->bcls", cc, bc)            # (B,nc,L,L)
    y_diag = jnp.einsum("bcls,bchls,bcshp->bclhp",
                        scores, lmat, xc.astype(jnp.float32))

    # --- per-chunk input -> state ---
    decay_to_end = jnp.exp(a_cs[..., -1:] - a_cs)             # (B,nc,H,L)
    states = jnp.einsum("bcsn,bchs,bcshp->bchpn",
                        bc, decay_to_end, xc.astype(jnp.float32))
    states = shard(states, "bchpn")

    # --- inter-chunk recurrence ---
    chunk_decay = jnp.exp(a_cs[..., -1])                      # (B,nc,H)

    def step(carry, xs):
        st_in, dec, st_chunk = carry, xs[0], xs[1]
        new = st_in * dec[..., None, None] + st_chunk
        return new, st_in                                     # emit state *before* chunk

    st0 = init_state if init_state is not None else \
        jnp.zeros((bsz, h, p, n), jnp.float32)
    final, prev_states = jax.lax.scan(
        step, st0,
        (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)        # (B,nc,H,P,N)

    # --- state -> output term ---
    in_decay = jnp.exp(a_cs)                                  # (B,nc,H,L)
    y_off = jnp.einsum("bcln,bchpn,bchl->bclhp", cc, prev_states, in_decay)

    y = (y_diag + y_off).reshape(bsz, nc * chunk, h, p)[:, :s]
    return y.astype(x.dtype), final


def mamba2_init_state(batch: int, cfg, dtype=jnp.float32) -> SSMState:
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_state
    return SSMState(
        ssm=jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32),
        conv=jnp.zeros((batch, cfg.conv_width - 1, conv_dim), dtype),
    )


def _window_at(window: jnp.ndarray, valid_len: jnp.ndarray,
               width: int) -> jnp.ndarray:
    """Per-row rolling conv state from a (B, W-1+S, C) window whose first
    W-1 rows are the incoming state and the rest the raw projections of a
    right-padded step: row ``b`` keeps rows ``valid_len[b] .. +W-2`` — the
    last W-1 *real* inputs (``dynamic_slice`` clamps in-range by
    construction since ``valid_len <= S``)."""
    return jax.vmap(
        lambda w, l: jax.lax.dynamic_slice_in_dim(w, l, width - 1, axis=0)
    )(window, valid_len)


def mamba2_block(p, x: jnp.ndarray, cfg,
                 state: Optional[SSMState] = None, quant: bool = False,
                 valid_len: Optional[jnp.ndarray] = None):
    """x: (B, S, d_model) -> (y, new_state).  Decode when ``state`` given.

    ``valid_len`` (B,) masks right-padding: pad tokens get ``dt = 0`` —
    decay ``exp(0) = 1`` and input contribution ``x * dt = 0``, so the
    recurrent state passes through them untouched — and the rolling conv
    window is sliced per row at the real-token boundary (``_window_at``).
    Two callers rely on it: bucketed prefill (one right-padded prompt into
    a fresh state) and chunked prefill (``forward(chunk_valid=...)``) —
    there the SAME masking runs mid-prompt, chunk by chunk, with the
    incoming state seeding the chunked dual form below; a ``valid_len[b]
    == 0`` row (a decode/free slot riding a chunk tick) passes through
    with state and conv window bit-identical."""
    bsz, s, _ = x.shape
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner = h * pdim
    conv_dim = d_inner + 2 * n

    # separate projections (z | x | B | C | dt): identical math to the fused
    # in_proj, but every split boundary is shard-aligned — the fused layout
    # forced GSPMD to reshard (full-tensor collective-permutes, §Perf log)
    if "in_proj" in p:                    # legacy fused layout
        zxbcdt = dense(p["in_proj"], x,
                       quant=p.get("in_proj_q") if quant else None, ctx=quant,
                       tp="col")
        # pin channels replicated before the split (CPU-SPMD hazard:
        # split/concat must never run along a sharded axis)
        zxbcdt = shard(zxbcdt, "btc", force=True)
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
        xs_r, b, c = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    else:
        z = dense(p["wz"], x, quant=p.get("wz_q") if quant else None,
                  ctx=quant, tp="col")
        xs_r = dense(p["wx"], x, quant=p.get("wx_q") if quant else None,
                     ctx=quant, tp="col")
        b = dense(p["wb"], x)
        c = dense(p["wc"], x)
        dt = dense(p["wdt"], x)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    if valid_len is not None:
        pad = jnp.arange(s, dtype=jnp.int32)[None, :] >= valid_len[:, None]
        dt = jnp.where(pad[..., None], 0.0, dt)
    a_log = -jnp.exp(p["a_log"].astype(jnp.float32))              # (H,) negative

    if state is None:
        xs_r = _causal_conv(xs_r, p["conv_wx"], p["conv_bx"])
        b = _causal_conv(b, p["conv_wb"], p["conv_bb"])
        c = _causal_conv(c, p["conv_wc"], p["conv_bc"])
        new_conv = None
    else:
        # the rolling window concats (xs|B|C) along channels and the conv
        # cache arrives model-sharded on that axis — pin every piece
        # replicated first: the channel axis must never be concat/split
        # while sharded (CPU-SPMD hazard, models/sharding.py::shard); the
        # window is (B, W-1+s, C)-tiny so replication costs nothing
        xs_r = shard(xs_r, "btc", force=True)
        b = shard(b, "btc", force=True)
        c = shard(c, "btc", force=True)
        conv_in = shard(state.conv, "btc", force=True)
        window = jnp.concatenate(
            [conv_in, jnp.concatenate([xs_r, b, c], -1).astype(
                conv_in.dtype)], axis=1)                          # (B, W-1+s, C)
        xbc_f = jnp.zeros((bsz, s, conv_dim), jnp.float32)
        # conv_wx/bx arrive channel-sharded from the param rules; their
        # concat with the replicated b/c conv weights runs along that axis
        # — same hazard, same cure (they're (W, C)-tiny)
        w = jnp.concatenate([replicate(p["conv_wx"]),
                             replicate(p["conv_wb"]),
                             replicate(p["conv_wc"])], -1)
        bias = jnp.concatenate([replicate(p["conv_bx"]),
                                replicate(p["conv_bb"]),
                                replicate(p["conv_bc"])], -1)
        w = w.astype(jnp.float32)
        width = w.shape[0]
        for i in range(width):
            xbc_f += window[:, i:i + s].astype(jnp.float32) * w[i]
        xbc = (xbc_f + bias.astype(jnp.float32)).astype(x.dtype)
        if valid_len is None:
            new_conv = window[:, s:s + cfg.conv_width - 1]
        else:
            new_conv = _window_at(window, valid_len, cfg.conv_width)
        # pin before the split: the downstream heads-sharding hint on xs
        # otherwise back-propagates through the reshape and re-shards this
        # very split (observed CPU-SPMD miscompile, tests/test_serve_sharded)
        xbc = shard(xbc, "btc", force=True)
        xs_r, b, c = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)

    xs = jax.nn.silu(xs_r.astype(jnp.float32)).astype(x.dtype)
    b = jax.nn.silu(b.astype(jnp.float32)).astype(x.dtype)
    c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
    xs = xs.reshape(bsz, s, h, pdim)
    xs = shard(xs, "bshp")

    a = dt * a_log                                               # (B,S,H)
    dx = xs.astype(jnp.float32) * dt[..., None]                  # dt folded into x

    if state is None:
        y, final = ssd_chunked(dx, a, b.astype(jnp.float32),
                               c.astype(jnp.float32), cfg.ssd_chunk)
        new_state = None
    elif s > cfg.conv_width:
        # prefill with state: chunked dual form seeded with the incoming
        # state — NOT the token recurrence (which would serialize 32k steps
        # and stream 100x the tensor bytes; §Perf iteration log)
        y, final = ssd_chunked(dx, a, b.astype(jnp.float32),
                               c.astype(jnp.float32), cfg.ssd_chunk,
                               init_state=state.ssm)
        new_state = SSMState(ssm=final, conv=new_conv)
    else:
        # short-step decode: pure recurrence, UNROLLED (s <= conv_width
        # here, so at most W steps) — a lax.scan at this spot nests three
        # deep at serve time (scheduler tick scan -> layer scan -> this);
        # unrolling is the faster lowering for a <= 4-step loop and one
        # fewer nested-scan level for the SPMD partitioner to get wrong.
        def step(st, dx_t, a_t, b_t, c_t):
            st = st * jnp.exp(a_t)[..., None, None] \
                + jnp.einsum("bhp,bn->bhpn", dx_t, b_t)
            y_t = jnp.einsum("bhpn,bn->bhp", st, c_t)
            return st, y_t
        bf = b.astype(jnp.float32)
        cf = c.astype(jnp.float32)
        st = state.ssm
        ys = []
        for t in range(s):
            st, y_t = step(st, dx[:, t], a[:, t], bf[:, t], cf[:, t])
            ys.append(y_t)
        final = st
        y = jnp.stack(ys, axis=1)                                 # (B,S,H,P)
        new_state = SSMState(ssm=final, conv=new_conv)

    y = y + xs.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    if state is not None:
        # serving path: the (H, P) -> d_inner merge below runs on a heads-
        # sharded tensor — pin it replicated first (CPU-SPMD hazard; decode
        # tensors are tick-sized, so the gather is noise).  The training
        # path keeps GSPMD's layout freedom.
        y = replicate(y)
    # back to the block io dtype — the SSD math runs f32; letting f32 leak
    # into out_proj doubles its dot + TP-reduce traffic (§Perf log)
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)
    z = z.astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm"], cfg.norm_eps)
    out = dense(p["out_proj"], y, quant=p.get("out_proj_q") if quant else None,
                ctx=quant, tp="row")
    if state is None:
        return out, None
    return out, new_state
