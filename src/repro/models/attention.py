"""GQA attention: flash-style chunked prefill/train + KV-cache decode.

Grouped formulation throughout — queries reshape to (B, S, G, R, D) with
G = kv heads and R = group size, so KV is *never* materialized repeated
(R x memory saving, and GSPMD keeps the cache sharding intact).

* ``flash_attention`` — online-softmax ``lax.scan`` over KV chunks; peak
  activation memory O(S * kv_chunk) per head instead of O(S^2): this is what
  lets 32k-prefill fit the dry-run memory budget.  Softmax statistics in f32.
* ``_decode_attention`` — single-token path: one masked einsum over the
  cache.  With the cache sequence-sharded on the TP axis the partial scores
  stay local and XLA inserts only tiny (B, G, R) softmax-stat collectives.
* ``_chunk_attention`` — chunked-prefill path (``attention(chunk_valid=)``):
  S chunk queries against the cache, same masked-einsum form as decode —
  mid-prompt chunks must see earlier chunks' K/V, which live in the cache,
  not in the fresh projections.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense, rms_norm
from repro.models.sharding import shard

NEG_INF = -1e30


def _grouped(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    q_positions: jnp.ndarray, kv_positions: jnp.ndarray,
                    causal: bool = True, kv_chunk: int = 1024,
                    kv_valid_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B, Sq, H, D); k/v: (B, Skv, G, D).  Returns (B, Sq, H, D).

    Custom VJP: the backward recomputes each chunk's P from the saved
    softmax statistics (O(S) residuals) — letting lax.scan's default VJP
    stack every chunk's (B,G,R,Sq,C) f32 probabilities measured +2 TB of
    HBM traffic per MoE train step (§Perf log).
    """
    b, sq, h, d = q.shape
    if sq == 1:
        return _decode_attention(q, k, v, q_positions, kv_positions,
                                 kv_valid_len)
    has_valid = kv_valid_len is not None
    fn = _make_flash(causal, min(kv_chunk, k.shape[1]), has_valid)
    valid = kv_valid_len if has_valid else jnp.zeros((b,), jnp.int32)
    return fn(q, k, v, q_positions, kv_positions, valid)


def _chunk_mask(pb, q_positions, ci, kv_chunk, valid, causal, has_valid):
    mask = jnp.ones((pb.shape[0], 1, 1, q_positions.shape[1],
                     pb.shape[1]), bool)
    if causal:
        mask = (pb[:, None, None, None, :]
                <= q_positions[:, None, None, :, None])
    if has_valid:
        idx = ci * kv_chunk + jnp.arange(kv_chunk)
        mask = mask & (idx[None, None, None, None, :]
                       < valid[:, None, None, None, None])
    return mask


import functools as _ft


@_ft.lru_cache(maxsize=None)
def _make_flash(causal: bool, kv_chunk: int, has_valid: bool):

    def _chunks(q, k, v, kv_positions):
        b, sq, h, d = q.shape
        skv, g = k.shape[1], k.shape[2]
        n_chunks = -(-skv // kv_chunk)
        pad = n_chunks * kv_chunk - skv
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                                   constant_values=2 ** 30)
        kc = k.reshape(b, n_chunks, kv_chunk, g, d).transpose(1, 0, 2, 3, 4)
        vc = v.reshape(b, n_chunks, kv_chunk, g, d).transpose(1, 0, 2, 3, 4)
        pc = kv_positions.reshape(b, n_chunks, kv_chunk).transpose(1, 0, 2)
        return kc, vc, pc, n_chunks, pad

    def _forward(q, k, v, q_positions, kv_positions, valid):
        b, sq, h, d = q.shape
        g = k.shape[2]
        r = h // g
        scale = 1.0 / jnp.sqrt(jnp.float32(d))
        kc, vc, pc, n_chunks, _ = _chunks(q, k, v, kv_positions)
        qg = _grouped(q, g)

        def body(carry, xs):
            m, l, acc = carry
            kb, vb, pb, ci = xs
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _chunk_mask(pb, q_positions, ci, kv_chunk, valid,
                               causal, has_valid)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(q.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, g, r, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, g, r, sq), jnp.float32)
        a0 = jnp.zeros((b, g, r, sq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (kc, vc, pc, jnp.arange(n_chunks)))
        out_g = acc / jnp.maximum(l[..., None], 1e-30)   # (B,G,R,Sq,D) f32
        lse = m + jnp.log(jnp.maximum(l, 1e-30))         # (B,G,R,Sq)
        out = out_g.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
        return out.astype(q.dtype), out_g, lse

    @jax.custom_vjp
    def flash(q, k, v, q_positions, kv_positions, valid):
        return _forward(q, k, v, q_positions, kv_positions, valid)[0]

    def fwd(q, k, v, q_positions, kv_positions, valid):
        out, out_g, lse = _forward(q, k, v, q_positions, kv_positions, valid)
        return out, (q, k, v, q_positions, kv_positions, valid, out_g, lse)

    def bwd(res, dout):
        q, k, v, q_positions, kv_positions, valid, out_g, lse = res
        b, sq, h, d = q.shape
        g = k.shape[2]
        scale = 1.0 / jnp.sqrt(jnp.float32(d))
        kc, vc, pc, n_chunks, pad = _chunks(q, k, v, kv_positions)
        qg = _grouped(q, g)
        do = _grouped(dout, g).transpose(0, 2, 3, 1, 4)  # (B,G,R,Sq,D) f32?
        do = do.astype(jnp.float32)
        # D_i = rowsum(dO * O)
        delta = jnp.sum(do * out_g, axis=-1)             # (B,G,R,Sq)

        def body(dq_acc, xs):
            kb, vb, pb, ci = xs
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _chunk_mask(pb, q_positions, ci, kv_chunk, valid,
                               causal, has_valid)
            s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse[..., None])              # (B,G,R,Sq,C)
            dv = jnp.einsum("bgrqk,bgrqd->bkgd", p.astype(q.dtype), do)
            dp = jnp.einsum("bgrqd,bkgd->bgrqk", do, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta[..., None]) * scale
            dsq = ds.astype(q.dtype)
            dq_c = jnp.einsum("bgrqk,bkgd->bqgrd", dsq, kb,
                              preferred_element_type=jnp.float32)
            dk = jnp.einsum("bgrqk,bqgrd->bkgd", dsq, qg,
                            preferred_element_type=jnp.float32)
            return dq_acc + dq_c, (dk, dv)

        dq0 = jnp.zeros(qg.shape, jnp.float32)
        dq_g, (dkc, dvc) = jax.lax.scan(
            body, dq0, (kc, vc, pc, jnp.arange(n_chunks)))
        dq = dq_g.reshape(b, sq, h, d).astype(q.dtype)
        skv_p = n_chunks * kv_chunk
        dk = dkc.transpose(1, 0, 2, 3, 4).reshape(b, skv_p, g, d)
        dv = dvc.transpose(1, 0, 2, 3, 4).reshape(b, skv_p, g, d)
        if pad:
            dk = dk[:, : k.shape[1]]
            dv = dv[:, : v.shape[1]]
        return (dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None, None)

    flash.defvjp(fwd, bwd)
    return flash


def _chunk_attention(q, k, v, q_positions, kv_positions, kv_valid_len):
    """q: (B, S, H, D) chunk queries against the full cache — the S-query
    generalization of :func:`_decode_attention`: one masked einsum + one
    softmax, no online chunking.

    Used by the chunked-prefill path, where queries must see *cache* rows
    (earlier chunks) and not just the fresh chunk K/V.  Like the decode path
    — and unlike ``flash_attention``, whose KV-chunk reshape would split the
    sequence axis (the documented CPU-SPMD hazard under a seq-sharded
    cache) — the scores stay shard-local and only softmax-normalization
    collectives cross shards.  Materializes (B, H, S, K) f32 scores: bounded
    by chunk_len x pool max_len, fine at serve-pool sizes (a flash-style
    online variant is the long-context follow-up).
    """
    b, sq, h, d = q.shape
    g = k.shape[2]
    qg = _grouped(q, g)                                  # (B, S, G, R, D)
    s = jnp.einsum("bsgrd,bkgd->bgrsk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d))
    mask = (kv_positions[:, None, None, None, :]
            <= q_positions[:, None, None, :, None])
    if kv_valid_len is not None:
        idx = jnp.arange(k.shape[1])
        mask = mask & (idx[None, None, None, None, :]
                       < kv_valid_len[:, None, None, None, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrsk,bkgd->bsgrd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _decode_attention(q, k, v, q_positions, kv_positions, kv_valid_len):
    """q: (B, 1, H, D) against the full cache — single masked einsum."""
    b, _, h, d = q.shape
    g = k.shape[2]
    qg = _grouped(q, g)[:, 0]                            # (B, G, R, D)
    # bf16 cache reads with f32 accumulation — the cache is never copied
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(d))
    mask = kv_positions[:, None, None, :] <= q_positions[:, None, None, :1]
    if kv_valid_len is not None:
        idx = jnp.arange(k.shape[1])
        mask = mask & (idx[None, None, None, :]
                       < kv_valid_len[:, None, None, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


class KVCache(NamedTuple):
    k: jnp.ndarray            # (B, S_max, G, D)
    v: jnp.ndarray
    length: jnp.ndarray       # () int32 — tokens currently valid


class PagedKVCache(NamedTuple):
    """Paged slot-pool KV (``serving/scheduler.py`` ``paged=True``).

    The pool stores fixed-size pages shared by every slot; each slot's
    logical ``(max_len, G, D)`` cache is the run of pages named by its
    page-table row.  Entry 0 is the reserved trash page — junk writes
    (inactive rows, pad positions) are redirected there instead of the
    dense path's "write back own bytes" trick (``serving.kvpool``).
    """
    k: jnp.ndarray            # (P, page_len, G, D) page pool
    v: jnp.ndarray
    page_table: jnp.ndarray   # (B, n_blocks) int32 page ids, 0 = trash
    length: jnp.ndarray       # (B,) int32 per-slot valid lengths


class QuantPagedKVCache(NamedTuple):
    """Log2-quantized page pool (``ServeScheduler(kv_quant=True)``).

    Pages hold packed ``core.logquant`` wire codes plus a per-(page, head)
    power-of-two scale exponent; the D&S-unit image of the paper's §IV
    claim applied to serving state — only ``kv_bits + 1`` bits per cache
    element move on the streaming path.  The per-slot *tail ring* keeps
    each slot's newest two pages dense in the cache dtype, so
    decode-adjacent tokens read exactly what the dense pool would hold
    (DESIGN.md §Quantized KV pages).  Every write is idempotent: a row's
    codes are a pure function of (value, its page's first-row scale), so
    the scheduler's masked junk-write/rewrite pattern reproduces identical
    bytes.
    """
    k_codes: jnp.ndarray      # (P, page_len, G, D) packed codes
    v_codes: jnp.ndarray
    k_scale: jnp.ndarray      # (P, G) int32 power-of-two scale exponents
    v_scale: jnp.ndarray
    k_tail: jnp.ndarray       # (B, 2*page_len + 1, G, D) dense tail ring
    v_tail: jnp.ndarray       # (row 2*page_len = junk bin)
    page_table: jnp.ndarray   # (B, n_blocks) int32 page ids, 0 = trash
    length: jnp.ndarray       # (B,) int32 per-slot valid lengths


def _paged_write(pool: jnp.ndarray, table: jnp.ndarray, new: jnp.ndarray,
                 pos: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """Scatter ``new`` (B, S, G, D) token rows into the page pool.

    ``pos`` (B, S) are absolute token positions (page = ``table[b,
    pos // page_len]``, offset = ``pos % page_len``); rows where ``keep``
    is False — chunk padding, positions past the slot's allocated pages —
    are redirected to the trash page.  Indexing stays in (page, offset)
    form end to end: no reshape ever merges the page axis with the
    in-page axis, so a page-sharded pool never sees a sharded-axis
    reshape (the documented CPU-SPMD hazard, models/sharding.py).
    """
    page_len = pool.shape[1]
    block = jnp.clip(pos // page_len, 0, table.shape[1] - 1)
    page = jnp.take_along_axis(table, block, axis=1)
    in_alloc = keep & (pos // page_len < table.shape[1])
    page = jnp.where(in_alloc, page, 0)
    off = jnp.where(in_alloc, pos % page_len, 0)
    flat_page = page.reshape(-1)
    flat_off = off.reshape(-1)
    vals = new.reshape((-1,) + new.shape[2:]).astype(pool.dtype)
    return pool.at[flat_page, flat_off].set(vals)


def _quant_paged_write(codes: jnp.ndarray, scale: jnp.ndarray,
                       tail: jnp.ndarray, table: jnp.ndarray,
                       new: jnp.ndarray, pos: jnp.ndarray, keep: jnp.ndarray,
                       start: jnp.ndarray, adv, n_bits: int):
    """Quantize-on-write into the compressed page pool + dense tail ring.

    ``codes (P, page_len, G, D)`` / ``scale (P, G)`` / ``tail (B,
    2*page_len + 1, G, D)``; ``new (B, S, G, D)`` rows at absolute
    positions ``pos (B, S)``; ``start (B,)`` is the pre-write length and
    ``adv`` the per-row advance.  Three scatters, all trash-redirected for
    masked rows exactly like :func:`_paged_write`:

    * codes — each row quantized under its page's scale.  A page whose
      first row sits inside this chunk takes its scale from that row; an
      older page reuses the pool's stored scale.  Appends only ever start
      a page at its offset-0 row, and the power-of-two scale makes
      requantization under the same scale lossless, so rewriting the same
      positions (the scheduler's junk-write pattern) reproduces identical
      bytes.
    * scale — only offset-0 rows own their page's scale entry; every other
      row's scale write is redirected to the trash page's entry.
    * tail ring — the row is also stored dense at ``pos % (2*page_len)``
      when it is within the newest two pages; older rows (and masked ones)
      hit the junk bin.  Two pages of ring mean a later write can only
      alias a position two pages back — one the overlay no longer reads.
    """
    from repro.core.logquant import quantize_page_codes, scale_exponent

    page_len = codes.shape[1]
    block = jnp.clip(pos // page_len, 0, table.shape[1] - 1)
    page = jnp.take_along_axis(table, block, axis=1)
    in_alloc = keep & (pos // page_len < table.shape[1])
    page = jnp.where(in_alloc, page, 0)
    off = jnp.where(in_alloc, pos % page_len, 0)

    b = pos.shape[0]
    startb = jnp.broadcast_to(start, (b,))
    p0 = pos - pos % page_len                     # each row's page start
    own = p0 >= startb[:, None]                   # page starts in this chunk
    j0 = jnp.clip(p0 - startb[:, None], 0, new.shape[1] - 1)
    row0 = jnp.take_along_axis(new, j0[..., None, None], axis=1)
    own_se = scale_exponent(row0, axis=-1)        # (B, S, G) int32
    pool_se = scale[page]                         # (B, S, G)
    se = jnp.where(own[..., None], own_se, pool_se)

    qcodes = quantize_page_codes(new, se[..., None], n_bits)
    codes = codes.at[page.reshape(-1), off.reshape(-1)].set(
        qcodes.reshape((-1,) + qcodes.shape[2:]).astype(codes.dtype))

    sp = jnp.where(in_alloc & (pos % page_len == 0), page, 0)
    scale = scale.at[sp.reshape(-1)].set(
        own_se.reshape((-1,) + own_se.shape[2:]))

    ring = 2 * page_len
    new_end = startb + adv
    in_ring = in_alloc & (pos >= new_end[:, None] - ring)
    toff = jnp.where(in_ring, pos % ring, ring)
    bidx = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                            pos.shape)
    tail = tail.at[bidx.reshape(-1), toff.reshape(-1)].set(
        new.reshape((-1,) + new.shape[2:]).astype(tail.dtype))
    return codes, scale, tail


def _quant_paged_gather(codes: jnp.ndarray, scale: jnp.ndarray,
                        tail: jnp.ndarray, table: jnp.ndarray,
                        lengths: jnp.ndarray, n_bits: int,
                        dtype) -> jnp.ndarray:
    """Dequant-fused gather of the compressed pool into the dense logical
    view, with the newest (possibly partial) page overlaid from the dense
    tail ring — so positions within two pages of the head are bit-equal to
    the dense pool's rows and older positions are their log2-quantized
    images.  Junk rows (trash pages, garbage scales) decode to finite
    values and are masked by the caller's ``kv_valid_len``."""
    from repro.core.logquant import dequantize_page_codes

    b, nb = table.shape
    page_len = codes.shape[1]
    deq = dequantize_page_codes(
        codes[table], scale[table][:, :, None, :, None], n_bits, dtype)
    tb = jnp.maximum(lengths - 1, 0) // page_len          # tail block
    half = (tb % 2) * page_len                            # ring half of tb
    j = jnp.arange(page_len, dtype=jnp.int32)
    tail_rows = jnp.take_along_axis(
        tail, (half[:, None] + j[None])[..., None, None], axis=1)
    use_tail = jnp.arange(nb, dtype=jnp.int32)[None] == tb[:, None]
    g = jnp.where(use_tail[:, :, None, None, None],
                  tail_rows[:, None].astype(dtype), deq)
    return g.reshape((b, nb * page_len) + codes.shape[2:])


def _paged_gather(pool: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Gather each slot's pages into its dense logical view: ``(P,
    page_len, G, D)`` pool + ``(B, n_blocks)`` table -> ``(B, n_blocks *
    page_len, G, D)`` — bytes at valid positions identical to the dense
    slab's, junk (trash/unwritten) rows masked by the caller's
    ``kv_valid_len`` exactly like dense-path padding."""
    b, nb = table.shape
    g = pool[table]                          # (B, nb, page_len, G, D)
    return g.reshape((b, nb * pool.shape[1]) + pool.shape[2:])


def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, length: int = 0) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        length=jnp.asarray(length, jnp.int32),
    )


def attention(p, x: jnp.ndarray, positions: jnp.ndarray, cfg,
              cache: Optional[KVCache] = None, quant=False,
              chunk_valid: Optional[jnp.ndarray] = None):
    """Full GQA block body (pre-norm residual handled by caller).

    Returns ``(attn_out, new_cache)``.  With ``cache`` given, ``x`` is the
    new-token slice (decode: S=1) appended at ``cache.length``.  ``quant``
    (bool | str | QuantCtx) routes QKV/O through the QeiHaN path.

    ``chunk_valid`` (``(B,)``, chunked prefill only) switches the
    prefill-with-cache path from "cache assumed empty" to *mid-prompt chunk*
    semantics: ``x`` is one right-padded chunk of a longer prompt whose
    earlier chunks already live in the cache.  Per row, only the first
    ``chunk_valid[b]`` slab positions are real — only those K/V rows are
    written (pad positions write back the cache's own bytes, an exact no-op)
    — and queries attend over the *cache* (earlier chunks + this one) under
    the causal mask with junk rows beyond ``length + chunk_valid`` masked by
    ``kv_valid_len``, instead of over the fresh chunk K/V alone.
    """
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = dense(p["wq"], x, p.get("bq"), p.get("wq_q") if quant else None,
              ctx=quant, tp="col")
    k = dense(p["wk"], x, p.get("bk"), p.get("wk_q") if quant else None,
              ctx=quant, tp="col")
    v = dense(p["wv"], x, p.get("bv"), p.get("wv_q") if quant else None,
              ctx=quant, tp="col")
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    # force=True pins head_dim (and any non-divisible heads dim) REPLICATED
    # before rope: rope splits/concats the head_dim axis, which GSPMD must
    # never see sharded (models/sharding.py::shard on the CPU-SPMD hazard)
    q = shard(q, "bthd", force=True)
    k = shard(k, "bthd", force=True)
    v = shard(v, "bthd", force=True)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = flash_attention(q, k, v, positions, positions, causal=True,
                              kv_chunk=cfg.kv_chunk)
        new_cache = None
    elif isinstance(cache, QuantPagedKVCache):
        # log2-quantized page pool: same (page, offset) addressing as the
        # PagedKVCache branch below, but rows quantize on write (packed
        # codes + per-page scale) and reads dequantize — fused into the
        # gather here, or into the Pallas kernel's per-page block loads.
        # The newest two pages stay dense in the tail ring, so
        # decode-adjacent tokens are bit-equal to the dense pool's
        # (DESIGN.md §Quantized KV pages).
        pos = cache.length[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        if chunk_valid is not None:
            keep = (jnp.arange(s, dtype=jnp.int32)[None]
                    < chunk_valid[:, None])
            adv = chunk_valid
        else:
            keep = jnp.ones((b, s), bool)
            adv = jnp.int32(s)
        n_bits = getattr(cfg, "kv_bits", 4)
        kcd, ksc, ktl = _quant_paged_write(
            cache.k_codes, cache.k_scale, cache.k_tail, cache.page_table,
            k, pos, keep, cache.length, adv, n_bits)
        vcd, vsc, vtl = _quant_paged_write(
            cache.v_codes, cache.v_scale, cache.v_tail, cache.page_table,
            v, pos, keep, cache.length, adv, n_bits)
        kcd = shard(kcd, "pool")
        vcd = shard(vcd, "pool")
        ktl = shard(ktl, "cache")
        vtl = shard(vtl, "cache")
        new_len = cache.length + adv
        if s == 1 and getattr(cfg, "paged_attn_kernel", "off") != "off":
            from repro.kernels.paged_attention.ops import \
                paged_decode_attention_quant
            out = paged_decode_attention_quant(
                q, kcd, ksc, vcd, vsc, ktl, vtl, cache.page_table, new_len,
                n_bits=n_bits, splits=getattr(cfg, "paged_attn_splits", 1))
        else:
            kg = _quant_paged_gather(kcd, ksc, ktl, cache.page_table,
                                     new_len, n_bits, ktl.dtype)
            vg = _quant_paged_gather(vcd, vsc, vtl, cache.page_table,
                                     new_len, n_bits, vtl.dtype)
            kv_pos = jnp.broadcast_to(
                jnp.arange(kg.shape[1], dtype=jnp.int32), (b, kg.shape[1]))
            if s == 1:
                out = _decode_attention(q, kg, vg, positions, kv_pos,
                                        kv_valid_len=new_len)
            else:
                out = _chunk_attention(q, kg, vg, positions, kv_pos,
                                       kv_valid_len=new_len)
        new_cache = QuantPagedKVCache(
            k_codes=kcd, v_codes=vcd, k_scale=ksc, v_scale=vsc,
            k_tail=ktl, v_tail=vtl, page_table=cache.page_table,
            length=new_len)
    elif isinstance(cache, PagedKVCache):
        # paged slot pool: per-page scatter writes + page-gathered reads.
        # Covers BOTH the decode step (S=1, every row appends at its own
        # length) and the chunked-prefill slab (chunk_valid real rows per
        # slot); the attention math is the same masked einsum as the dense
        # paths over the gathered view, so valid positions are bit-equal.
        # With cfg.paged_attn_kernel != "off" the S=1 decode read skips the
        # dense gather entirely: the Pallas kernel walks the page table
        # (token-equal on every tested seed; logits to f32-ULP softmax
        # reassociation — DESIGN.md §Paged attention kernel).
        pos = cache.length[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        if chunk_valid is not None:
            keep = (jnp.arange(s, dtype=jnp.int32)[None]
                    < chunk_valid[:, None])
            adv = chunk_valid
        else:
            keep = jnp.ones((b, s), bool)
            adv = jnp.int32(s)
        kc = _paged_write(cache.k, cache.page_table, k, pos, keep)
        vc = _paged_write(cache.v, cache.page_table, v, pos, keep)
        kc = shard(kc, "pool")
        vc = shard(vc, "pool")
        new_len = cache.length + adv
        if s == 1 and getattr(cfg, "paged_attn_kernel", "off") != "off":
            # fused table-walk kernel: per-page K/V loads + online softmax
            # straight off the pool — the dense gather below never runs.
            # Decode masking is the single `pos < new_len` predicate (the
            # causal mask is the same set at q_pos = new_len - 1); split-KV
            # partials merge outside the kernel (kernels/paged_attention).
            from repro.kernels.paged_attention.ops import \
                paged_decode_attention
            out = paged_decode_attention(
                q, kc, vc, cache.page_table, new_len,
                splits=getattr(cfg, "paged_attn_splits", 1))
        elif s == 1:
            kg = _paged_gather(kc, cache.page_table)
            vg = _paged_gather(vc, cache.page_table)
            kv_pos = jnp.broadcast_to(
                jnp.arange(kg.shape[1], dtype=jnp.int32), (b, kg.shape[1]))
            out = _decode_attention(q, kg, vg, positions, kv_pos,
                                    kv_valid_len=new_len)
        else:
            kg = _paged_gather(kc, cache.page_table)
            vg = _paged_gather(vc, cache.page_table)
            kv_pos = jnp.broadcast_to(
                jnp.arange(kg.shape[1], dtype=jnp.int32), (b, kg.shape[1]))
            out = _chunk_attention(q, kg, vg, positions, kv_pos,
                                   kv_valid_len=new_len)
        new_cache = PagedKVCache(k=kc, v=vc, page_table=cache.page_table,
                                 length=new_len)
    elif chunk_valid is not None:
        # chunked prefill: write ONLY the real slab rows (pad positions
        # write the cache's own bytes back — an exact no-op, so a decode /
        # free row riding along with chunk_valid == 0 leaves its cache
        # untouched), then attend over the cache: earlier chunks are already
        # resident and this chunk was just appended.  Cache row i holds the
        # token at position i, so the causal mask is plain kv_pos <= q_pos
        # and kv_valid_len hides junk rows beyond each row's new length.
        idx = jnp.broadcast_to(cache.length, (b,))

        def chunk_upd(c, n, i, keep_r):
            cur = jax.lax.dynamic_slice_in_dim(c, i, n.shape[0], axis=0)
            slab = jnp.where(keep_r[:, None, None], n, cur)
            return jax.lax.dynamic_update_slice_in_dim(c, slab, i, axis=0)

        keep = jnp.arange(s, dtype=jnp.int32)[None, :] < chunk_valid[:, None]
        row_upd = jax.vmap(chunk_upd)
        kc = row_upd(cache.k, k.astype(cache.k.dtype), idx, keep)
        vc = row_upd(cache.v, v.astype(cache.v.dtype), idx, keep)
        kc = shard(kc, "cache")
        vc = shard(vc, "cache")
        new_len = idx + chunk_valid
        kv_pos = jnp.broadcast_to(
            jnp.arange(kc.shape[1], dtype=jnp.int32), (b, kc.shape[1]))
        out = _chunk_attention(q, kc, vc, positions, kv_pos,
                               kv_valid_len=new_len)
        new_cache = KVCache(k=kc, v=vc, length=new_len)
    else:
        idx = cache.length
        if getattr(idx, "ndim", 0):
            # per-slot (B,) lengths: each row appends at its own offset —
            # vmapped dynamic-update keeps the write in-place per row
            row_upd = jax.vmap(
                lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
                    c, n, i, axis=0))
            kc = row_upd(cache.k, k.astype(cache.k.dtype), idx)
            vc = row_upd(cache.v, v.astype(cache.v.dtype), idx)
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), idx, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), idx, axis=1)
        kc = shard(kc, "cache")
        vc = shard(vc, "cache")
        new_len = idx + s
        if s == 1:
            kv_pos = jnp.broadcast_to(
                jnp.arange(kc.shape[1], dtype=jnp.int32), (b, kc.shape[1]))
            out = flash_attention(q, kc, vc, positions, kv_pos, causal=True,
                                  kv_chunk=cfg.kv_chunk,
                                  kv_valid_len=jnp.broadcast_to(new_len, (b,)))
        else:
            # prefill (cache assumed empty before this call): attend over the
            # fresh K/V — avoids streaming the seq-sharded cache back through
            # the chunk scan (the cache write above is the only cache access)
            out = flash_attention(q, k, v, positions, positions, causal=True,
                                  kv_chunk=cfg.kv_chunk)
        new_cache = KVCache(k=kc, v=vc, length=new_len)

    out = out.reshape(b, s, h * hd)
    y = dense(p["wo"], out, quant=p.get("wo_q") if quant else None, ctx=quant,
              tp="row")
    return y, new_cache
