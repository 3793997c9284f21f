"""Public jit'd wrapper for the paged-attention decode kernel.

Responsibilities: grouped-query reshape, split-KV table padding (trailing
trash-page columns make the block count divisible by ``splits`` — padded
blocks sit past every valid position, so they mask to exact zeros), the
cross-split partial-softmax merge, the split-KV sharding hints, and the
gather-traffic accounting benchmarks report (the paper-§IV "avoided
accesses" image of the kernel, like ``bitplane_matmul.ops
.plane_traffic_fraction`` for weight planes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.kernel import (NEG_INF,
                                                  paged_attention_kernel,
                                                  paged_attention_quant_kernel)
from repro.models.sharding import kernel_call, kernel_mesh, shard

# per-dim roles of the kernel operands under a mesh (models.sharding
# .kernel_call): slots on the batch axes, kv heads on the model axis; the
# pool is read whole on every device of a batch shard (page ids are global)
_Q, _POOL, _ROW, _LEN = ("b", "m", None, None), (None, None, "m", None), \
    ("b", None), ("b",)
_PARTIALS = [("b", None, "m", None, None), ("b", None, "m", None),
             ("b", None, "m", None)]


def merge_split_softmax(m: jnp.ndarray, l: jnp.ndarray, acc: jnp.ndarray,
                        axis: int = -1) -> jnp.ndarray:
    """Reduce per-split online-softmax partials into the full softmax.

    ``m`` / ``l`` carry a split axis at ``axis``; ``acc`` carries the same
    axis plus a trailing feature dim.  With the global max ``M`` over
    splits, each split reweights by ``exp(m - M)`` — for a split that saw
    no valid token ``m == NEG_INF`` (finite, -1e30) and the weight
    underflows to exactly 0.0 in f32, so its junk partials are *bitwise*
    absent from the sum.  A row with no valid token anywhere keeps
    ``l_tot`` positive (every split contributes its uniform-junk ``l``),
    so the output is finite garbage — never NaN — exactly like the dense
    path's softmax over an all-NEG_INF row; such rows are inactive slots
    whose outputs the serve tick discards.
    """
    axis = axis % m.ndim          # acc has a trailing extra dim, so resolve
    m_max = jnp.max(m, axis=axis, keepdims=True)  # negative axes against m
    w = jnp.exp(m - m_max)
    l_tot = jnp.sum(l * w, axis=axis)
    num = jnp.sum(acc * jnp.expand_dims(w, -1), axis=axis)
    return num / jnp.maximum(l_tot, 1e-30)[..., None]


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, *, splits: int = 1,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Decode attention straight off the page pool — no dense gather.

    q (B, 1, H, D); k/v pool (P, page_len, G, D); page_table (B, NB)
    int32 (entry 0 = trash page); lengths (B,) int32 valid tokens per row.
    Returns (B, 1, H, D) in q's dtype — the drop-in replacement for
    ``_paged_gather`` + ``_decode_attention`` (token-equal on every tested
    seed/arch; logits agree to f32-ULP softmax reassociation, see
    tests/test_paged_attention.py for the exact bar).  Under a
    multi-device mesh the kernel runs per device (``kernel_call``).
    """
    return _paged_decode_attention(q, k_pool, v_pool, page_table, lengths,
                                   splits=splits, interpret=interpret,
                                   kmesh=kernel_mesh())


@functools.partial(jax.jit, static_argnames=("splits", "interpret", "kmesh"))
def _paged_decode_attention(q, k_pool, v_pool, page_table, lengths, *,
                            splits, interpret, kmesh):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, _, h, d = q.shape
    g = k_pool.shape[2]
    nb = page_table.shape[1]
    pad = (-nb) % splits
    if pad:
        # trash-page columns: their positions sit past any valid length,
        # so the kernel masks them to exact zeros like any junk tail
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
    qg = q.reshape(b, 1, g, h // g, d)[:, 0]             # (B, G, R, D)
    kern = functools.partial(paged_attention_kernel, splits=splits,
                             interpret=interpret)
    o, m, l = kernel_call(kern, kmesh, (_Q, _POOL, _POOL, _ROW, _LEN),
                          _PARTIALS, qg, k_pool, v_pool,
                          page_table.astype(jnp.int32),
                          lengths.astype(jnp.int32))
    # split-KV partial-reduce rule: the split axis may ride the model mesh
    # axis (models.sharding "kvsplit" kinds; launch.shardings
    # .split_kv_specs documents the layout) — each shard owns a contiguous
    # page run, the merge below is the only cross-shard reduction
    o = shard(o, "kvsplit")
    m = shard(m, "kvsplit_stat")
    l = shard(l, "kvsplit_stat")
    out = merge_split_softmax(m, l, o, axis=1)           # (B, G, R, D)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_decode_attention_quant(q: jnp.ndarray, k_codes: jnp.ndarray,
                                 k_scale: jnp.ndarray, v_codes: jnp.ndarray,
                                 v_scale: jnp.ndarray, k_tail: jnp.ndarray,
                                 v_tail: jnp.ndarray, page_table: jnp.ndarray,
                                 lengths: jnp.ndarray, *, n_bits: int = 4,
                                 splits: int = 1,
                                 interpret: bool | None = None) -> jnp.ndarray:
    """Decode attention off the log2-quantized page pool.

    q (B, 1, H, D); code pools (P, page_len, G, D) packed wire codes;
    scale pools (P, G) int32; tail rings (B, 2*page_len + 1, G, D) dense
    cache-dtype (row 2*page_len = junk bin); page_table (B, NB) int32;
    lengths (B,) int32.  The kernel walks *full* pages only (lengths
    floored to a page multiple — the newest partial page's codes are
    still being rewritten every tick); the partial page is computed here
    as one extra dense flash-decode split over the tail ring and merged
    through the same :func:`merge_split_softmax`, so its tokens read
    exactly the bytes the dense pool would hold.
    """
    return _paged_decode_attention_quant(
        q, k_codes, k_scale, v_codes, v_scale, k_tail, v_tail, page_table,
        lengths, n_bits=n_bits, splits=splits, interpret=interpret,
        kmesh=kernel_mesh())


@functools.partial(jax.jit, static_argnames=("n_bits", "splits", "interpret",
                                             "kmesh"))
def _paged_decode_attention_quant(q, k_codes, k_scale, v_codes, v_scale,
                                  k_tail, v_tail, page_table, lengths, *,
                                  n_bits, splits, interpret, kmesh):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, _, h, d = q.shape
    page_len = k_codes.shape[1]
    g = k_codes.shape[2]
    nb = page_table.shape[1]
    pad = (-nb) % splits
    if pad:
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
    qg = q.reshape(b, 1, g, h // g, d)[:, 0]             # (B, G, R, D)
    lengths = lengths.astype(jnp.int32)
    tb = jnp.maximum(lengths - 1, 0) // page_len         # tail-page block
    kern_lens = tb * page_len                            # full pages only
    kern = functools.partial(paged_attention_quant_kernel, n_bits=n_bits,
                             splits=splits, interpret=interpret)
    o, m, l = kernel_call(kern, kmesh,
                          (_Q, _POOL, (None, "m"), _POOL, (None, "m"), _ROW,
                           _LEN),
                          _PARTIALS, qg, k_codes, k_scale, v_codes, v_scale,
                          page_table.astype(jnp.int32), kern_lens)
    o = shard(o, "kvsplit")
    m = shard(m, "kvsplit_stat")
    l = shard(l, "kvsplit_stat")

    # the tail-page partial: ring half (tb % 2) * page_len holds positions
    # [tb*page_len, (tb+1)*page_len) — a flash-decode block over dense rows
    half = (tb % 2) * page_len
    j = jnp.arange(page_len, dtype=jnp.int32)
    idx = (half[:, None] + j[None, :])[:, :, None, None]
    kt = jnp.take_along_axis(k_tail, idx, axis=1)        # (B, pl, G, D)
    vt = jnp.take_along_axis(v_tail, idx, axis=1)
    pos = tb[:, None] * page_len + j[None, :]            # (B, pl) absolute
    # the kernel's matmul precision (f32 queries at full f32 precision)
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s_t = jnp.einsum("bgrd,bkgd->bgrk", qg.astype(jnp.float32),
                     kt.astype(jnp.float32),
                     precision=prec) / jnp.sqrt(jnp.float32(d))
    s_t = jnp.where(pos[:, None, None, :] < lengths[:, None, None, None],
                    s_t, NEG_INF)
    m_t = jnp.max(s_t, axis=-1, keepdims=True)           # (B, G, R, 1)
    # p casts to the cache dtype before PV, mirroring the dense decode
    # path — the tail tokens must read exactly like the dense pool's
    p = jnp.exp(s_t - m_t)
    l_t = jnp.sum(p, axis=-1)                            # (B, G, R)
    acc_t = jnp.einsum("bgrk,bkgd->bgrd", p.astype(vt.dtype), vt,
                       precision=prec, preferred_element_type=jnp.float32)

    # append the tail as one extra split: kernel partials are UNNORMALIZED
    # accumulators, so the tail block composes through the same merge
    o = jnp.concatenate([o, acc_t[:, None]], axis=1)
    m = jnp.concatenate([m, m_t[..., 0][:, None]], axis=1)
    l = jnp.concatenate([l, l_t[:, None]], axis=1)
    out = merge_split_softmax(m, l, o, axis=1)           # (B, G, R, D)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def gather_traffic_counts(page_table: np.ndarray, lengths: np.ndarray,
                          page_len: int):
    """(touched, total) page-read counts per decode tick, as floats.

    ``total`` is what the dense ``pool[table]`` gather streams — every
    allocated table column of every slot, valid or not; ``touched`` is
    what the kernel's table walk reads — only pages holding at least one
    valid token (``ceil(length / page_len)``).  The ratio is the paged
    analogue of ``plane_traffic_fraction``: deterministic, exact, gated
    by the ``paged_attn`` bench baseline.
    """
    table = np.asarray(page_table)
    lens = np.asarray(lengths)
    total = float(table.shape[0] * table.shape[1])
    touched = float(np.sum(-(-lens // int(page_len))))
    return touched, total
