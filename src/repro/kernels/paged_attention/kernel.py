"""Pallas kernel: paged-attention decode with split-KV flash-decode.

Paper mapping (arXiv 2310.18181; DESIGN.md §Paged attention kernel): the
paper's §IV thesis is that DNN inference is bounded by *memory accesses*,
and its in-memory scheme wins by touching only the rows a computation
actually needs.  The serving-side image of that is this kernel: instead of
gathering ``pool[table]`` into a dense padded ``(B, max_len, G, D)`` view
every decode tick (reading ALL allocated pages of every slot, valid or
not), the BlockSpec index maps below dereference the scalar-prefetched
page table themselves — a grid step of slot ``b`` loads the pool pages
its columns of ``table[b]`` name directly, so only resident pages ever
stream into VMEM and nothing is ever re-laid-out densely.

**Block walk.** A grid step covers ``ppb`` consecutive table columns
(:func:`block_pages`: about 128 tokens, 8 pages of 16).  The K and V
pools are each passed ``ppb`` times, one one-page BlockSpec per column,
so the step's pages arrive through Pallas's own pipelined copies.  Page
operand ``i`` of block ``j`` of slot ``b`` names

    table[b, min(j * ppb + i, own_i(b))],
    last(b)  = max(ceil(length_b / page_len) - 1, 0)
    own_i(b) = last(b) - (last(b) - i) mod ppb   (i <= last(b), else last(b))

``own_i`` is the last live column that operand ``i`` walks, so past the
slot's length every operand names the page it named the step before.  A
block wholly past the length then repeats the previous step's pages, the
pipeline issues no copy, and the body's ``pl.when(block_start <
length)`` skips its compute; in the last live block the operands past
the length hold earlier live pages, which the position mask erases.
Dead table columns are never read at all, and a slot's copies beyond
its live pages are the ``ppb - 1 - last`` repeats of a slot shorter than
one block.  The clamp is applied once a call, in XLA, as a ``(B, NB)``
walk table (:func:`clamped_walk`) that the index maps read as they
would the page table: evaluating it inside 16 index maps cost about
0.7 us a grid step on a v5e.  ``ppb`` depends only on ``page_len`` and
the table width, never on ``splits``: block boundaries are then the
same for every split count, and a split that holds no valid token stays
bitwise absent from the merge.

Per (slot, split) the kernel walks that split's blocks in order with
the standard online-softmax recurrence (running max ``m``, running
normalizer ``l``, rescaled accumulator ``acc`` — the same f32
statistics ``models.attention.flash_attention`` carries over KV chunks).
Per kv head a block's pages join into one ``(ppb * page_len, D)`` K and
V operand, so one score matmul, one softmax update and one PV matmul
cover the block:

    s_j  = (q @ k_j^T) / sqrt(D),  masked to  pos < length  with the
           finite NEG_INF = -1e30 (never -inf: all-masked blocks then
           yield exp(0)=1 "uniform junk" instead of inf-inf NaNs, and the
           junk is *exactly* erased later — see below)
    m'   = max(m, max_k s_j)
    p    = exp(s_j - m');  corr = exp(m - m')
    l    = l * corr + sum_k p;   acc = acc * corr + p @ v_j

**Split-KV ("flash-decode", SNIPPETS.md flashdecode idiom)**: the block
axis is additionally partitioned into ``splits`` contiguous runs mapped
to a parallel grid axis; each run emits partial ``(acc, m, l)`` and the
tiny cross-split merge happens outside the kernel
(``ops.merge_split_softmax``).  A split that holds no valid token
computes nothing and emits ``m = NEG_INF``, ``l = 0``, ``acc = 0``; the
merge weights it by ``exp(NEG_INF - m_real) == 0.0`` exactly (f32
underflow), so such splits are *bitwise* absent from the output.  Under
a mesh the split axis can ride the ``model`` axis
(``launch.shardings.split_kv_specs``), so each shard reads only its own
pages and ships one (B, G, R)-sized statistic triple.

Masking is the single ``pos < length`` predicate: decode queries sit at
position ``length - 1``, so the dense path's causal mask (``kv_pos <=
q_pos``) and validity mask (``kv_pos < length``) are the same set.

Grid: ``(B, splits, blocks_per_split)``, blocks innermost
(accumulator-friendly, "arbitrary"); q/out blocks are whole (G, R, D)
tiles — R and D are small (<= head_dim) so VMEM residency is a few KiB
per step.  The quantized-pool kernel keeps one page per step and walks
every column (``ppb = 1``, no clamp, no skip).  On this CPU container
the kernels run in interpret mode (the wrapper auto-selects), which
lowers to plain traced lax ops — jittable, scannable inside the serve
tick, and partitionable by GSPMD.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.logquant import pow2_exact

NEG_INF = -1e30

# The long-context ragged decode tick used by BOTH the paged_attn kernel
# microbench (benchmarks/kernel_bench.py) and the static kernel verifier
# (analysis.kernel_rules) — one geometry, one gather_saved_frac number,
# EXACT-gated in benchmarks/baselines/{paged_attn,kernel_audit}.json.
RAGGED512 = dict(b=4, page_len=16, nb=32, g=2, r=2, d=16,
                 lengths=(512, 300, 64, 17))

# tokens per grid step of the float kernel (block_pages)
BLOCK_TOKENS = 128

# slot lengths of the static verifier's qwen2.5-14b-l12 decode case: 21
# live slots, as in the cell, from each side of the 128-token block
# boundary and lengths of the cell's traffic up to the full 4,160 table,
# and 11 free (1,656 of the 2,400 pages)
QWEN14B_LENGTHS = (0, 1, 127, 128, 129, 255, 256, 257, 300, 512, 777,
                   1000, 1024, 1300, 1536, 1700, 2000, 2100, 2500, 3000,
                   3300, 4160, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0)


def block_pages(page_len: int, nb: int) -> int:
    """Pages per grid step of the float kernel: a block of about 128
    tokens (8 pages of 16), never wider than the table.  A function of
    the shapes alone — never of ``splits``, never a knob — so block
    boundaries are the same for every split count."""
    return min(max(1, BLOCK_TOKENS // page_len), nb)


def clamped_walk(table, lengths, page_len: int, ppb: int):
    """The table the float kernel's page operands read: column ``c`` of
    row ``b`` names ``table[b, min(c, own)]``, where ``own`` is the last
    live column that operand ``c mod ppb`` walks (``last - (last - i) mod
    ppb`` for ``i <= last``, else ``last``; ``last = max(ceil(length /
    page_len) - 1, 0)``).  Past a row's length every operand so names the
    page it named the step before, and the pipeline issues no copy.  One
    small XLA gather a call, ``(B, NB)`` int32; numpy inputs give the
    same table (the static verifier's)."""
    nb = table.shape[1]
    last = jnp.maximum((jnp.asarray(lengths) + page_len - 1) // page_len
                       - 1, 0)[:, None]
    col = jnp.arange(nb, dtype=jnp.int32)[None, :]
    i = col % ppb
    own = jnp.where(i <= last, last - (last - i) % ppb, last)
    return jnp.take_along_axis(jnp.asarray(table), jnp.minimum(col, own),
                               axis=1)


def paged_attn_specs(b: int, g: int, r: int, d: int, page_len: int,
                     nb: int, splits: int, ppb: int = 1):
    """Grid + BlockSpecs + scratch of one kernel instantiation.

    ONE source of truth: :func:`paged_attention_kernel` assembles its
    ``PrefetchScalarGridSpec`` from exactly this, and each ``audit_specs``
    instantiation hands the same objects to the static verifier
    (``analysis.pallas_inspect``) — so the index maps the verifier proves
    in-bounds are the index maps the kernel ships, not a re-statement.

    Every block's last two dims are the operand's full last two dims, the
    form Mosaic accepts whatever G, R and D are: a K/V block is one whole
    page ``(1, page_len, G, D)`` with all kv heads, q is ``(1, G, R, D)``,
    and the outputs carry the split axis ahead of ``(G, R[, D])``.  The
    inputs are q, then ``ppb`` page specs for K, then ``ppb`` for V; the
    grid walks ``nb / ppb`` blocks of ``ppb`` pages, page operand ``i`` of
    block ``blk = split * bps + j`` reading column ``blk * ppb + i`` of
    the prefetched table (the float kernel's is :func:`clamped_walk`).
    """
    assert nb % (splits * ppb) == 0, (nb, splits, ppb)
    bps = nb // (splits * ppb)
    grid = (b, splits, bps)

    def page(i):
        return pl.BlockSpec((1, page_len, g, d),
                            lambda bi, si, ji, tab, lens:
                            (tab[bi, (si * bps + ji) * ppb + i], 0, 0, 0))
    pages = [page(i) for i in range(ppb)]
    in_specs = [pl.BlockSpec((1, g, r, d),
                             lambda bi, si, ji, tab, lens: (bi, 0, 0, 0)),
                *pages, *pages]
    stat = pl.BlockSpec((1, 1, g, r),
                        lambda bi, si, ji, tab, lens: (bi, si, 0, 0))
    out_specs = [
        pl.BlockSpec((1, 1, g, r, d),
                     lambda bi, si, ji, tab, lens: (bi, si, 0, 0, 0)),
        stat,
        stat,
    ]
    scratch_shapes = [pltpu.VMEM((g, r, 1), jnp.float32),
                      pltpu.VMEM((g, r, 1), jnp.float32),
                      pltpu.VMEM((g, r, d), jnp.float32)]
    return grid, in_specs, out_specs, scratch_shapes, bps


def paged_attn_quant_specs(b: int, g: int, r: int, d: int, page_len: int,
                           nb: int, splits: int):
    """Quantized-pool variant of :func:`paged_attn_specs`: one page per
    step, every column walked as the table stands (``ppb = 1``).

    Same grid/out/scratch; the K/V operands are packed log2 code pools
    (same (P, page_len, G, D) geometry, int8/int16 elements — the §IV
    traffic saving is the dtype shrink on exactly these block loads) plus
    one int32 scale-exponent pool each, viewed as (P, 1, G) so that the
    current page's row is a full-dim ``(1, 1, G)`` block; it goes to SMEM
    through the same page-table walk (the kernel reads one scalar per
    head).
    """
    grid, in_specs, out_specs, scratch_shapes, bps = paged_attn_specs(
        b, g, r, d, page_len, nb, splits)
    scale_spec = pl.BlockSpec(
        (1, 1, g),
        lambda bi, si, ji, tab, lens: (tab[bi, si * bps + ji], 0, 0),
        memory_space=pltpu.SMEM)
    in_specs = [in_specs[0], in_specs[1], scale_spec, in_specs[2],
                scale_spec]
    return grid, in_specs, out_specs, scratch_shapes, bps


def _dequant_block(codes, se, n_bits: int):
    """In-kernel log2 dequant of one page block: ``sign * 2^(exp + se)``
    with the zero sentinel -> 0.  The summed exponent clamps to the f32
    normal range so garbage codes/scales (trash-page contents) decode to
    large-but-finite values the position mask then erases — never Inf/NaN
    (mirrors ``core.logquant.dequantize_page_codes``)."""
    sentinel = -(1 << (n_bits - 1))
    c = codes.astype(jnp.int32)
    e = c >> 1
    mag = pow2_exact(jnp.clip(e + se, -126, 127))
    val = jnp.where((c & 1) != 0, -mag, mag)
    return jnp.where(e == sentinel, 0.0, val)


def _online_softmax_step(q, k, v, g, pos, length, m_s, l_s, acc_s, *,
                         zero_masked_p: bool):
    """One block of kv head ``g``: fold ``(k, v)`` into the running
    ``(m, l, acc)`` statistics of that head's R query rows.  f32 operands
    multiply at full f32 precision (Mosaic's default passes bf16)."""
    prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))    # (R, block tokens)
    valid = pos < length
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_s[g]                               # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if zero_masked_p:
        # all-masked-so-far blocks keep m_new = NEG_INF, so masked p would
        # be exp(0) = 1 against dequantized garbage of magnitude up to
        # 2^127 — large enough for the junk accumulator to overflow to inf
        # and turn the merge's zero weight into 0 * inf = NaN.  Zero the
        # masked p explicitly: bitwise no-op for any block holding a valid
        # token (there masked p already underflowed to exact 0.0)
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[g] = l_s[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
    # p is cast to the cache dtype before the PV product, mirroring the
    # dense path's `p.astype(q.dtype)` — keeps kernel-vs-dense drift to
    # the softmax reassociation alone
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             precision=prec,
                             preferred_element_type=jnp.float32)
    acc_s[g] = acc_s[g] * corr + pv
    m_s[g] = m_new


def _init_stats(m_s, l_s, acc_s):
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)


def _flush_stats(o_ref, m_ref, l_ref, m_s, l_s, acc_s):
    o_ref[0, 0] = acc_s[...]
    m_ref[0, 0] = m_s[..., 0]
    l_ref[0, 0] = l_s[..., 0]


def _block_start(block_len: int, bps: int):
    """The absolute position of this grid step's first token."""
    return (pl.program_id(1) * bps + pl.program_id(2)) * block_len


def _positions(start, block_len: int):
    """(1, block_len) absolute token positions from ``start``."""
    return start + jax.lax.broadcasted_iota(jnp.int32, (1, block_len), 1)


def _paged_attn_kernel(walk_ref, lens_ref,       # scalar prefetch
                       q_ref,                    # (1, G, R, D)
                       *refs,                    # ppb K pages, ppb V pages
                       page_len: int, bps: int, ppb: int):
    # refs: ppb K then ppb V (1, page_len, G, D) pages, then the outputs
    # o (1, 1, G, R, D) and m, l (1, 1, G, R) f32, then the VMEM scratch
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref, m_s, l_s, acc_s = refs[2 * ppb:]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_stats(m_s, l_s, acc_s)

    start = _block_start(ppb * page_len, bps)
    length = lens_ref[pl.program_id(0)]

    # a block wholly past the length: its pages were not copied (the
    # clamped walk repeats the previous step's) and it folds nothing
    @pl.when(start < length)
    def _block():
        pos = _positions(start, ppb * page_len)
        for g in range(q_ref.shape[1]):           # static: G kv heads
            k = jnp.concatenate([r[0, :, g, :] for r in k_refs], axis=0)
            v = jnp.concatenate([r[0, :, g, :] for r in v_refs], axis=0)
            _online_softmax_step(q_ref[0, g], k, v, g, pos, length,
                                 m_s, l_s, acc_s, zero_masked_p=False)

    @pl.when(j == bps - 1)
    def _flush():
        _flush_stats(o_ref, m_ref, l_ref, m_s, l_s, acc_s)


def _partials_shape(b: int, g: int, r: int, d: int, splits: int):
    return [jax.ShapeDtypeStruct((b, splits, g, r, d), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, g, r), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, g, r), jnp.float32)]


def paged_attention_kernel(qg: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, *, splits: int = 1,
                           ppb: int, interpret: bool = False):
    """qg (B, G, R, D) grouped decode queries; k/v pool (P, page_len, G,
    D); page_table (B, NB) int32 with NB divisible by ``splits * ppb``
    (``ppb`` pages per grid step, :func:`block_pages` of the unpadded
    table), read through its :func:`clamped_walk`; lengths (B,) int32.  Returns partial ``(o, m, l)``: o (B,
    splits, G, R, D) f32, m/l (B, splits, G, R) f32 — merge with
    :func:`ops.merge_split_softmax`."""
    b, g, r, d = qg.shape
    page_len = k_pool.shape[1]
    nb = page_table.shape[1]
    grid, in_specs, out_specs, scratch_shapes, bps = paged_attn_specs(
        b, g, r, d, page_len, nb, splits, ppb)
    walk = clamped_walk(page_table, lengths, page_len, ppb)

    kern = functools.partial(_paged_attn_kernel, page_len=page_len, bps=bps,
                             ppb=ppb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_partials_shape(b, g, r, d, splits),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(walk, lengths, qg, *[k_pool] * ppb, *[v_pool] * ppb)


def _paged_attn_quant_kernel(table_ref, lens_ref,  # scalar prefetch
                             q_ref,                # (1, G, R, D)
                             k_ref, ks_ref,        # (1, page_len, G, D) codes
                             v_ref, vs_ref,        # + (1, 1, G) int32 scales (SMEM)
                             o_ref, m_ref, l_ref,
                             m_s, l_s, acc_s,
                             *, page_len: int, bps: int, n_bits: int):
    """Quantized-pool body: identical online-softmax walk to
    :func:`_paged_attn_kernel`, but each page block streams in as packed
    log2 codes + one scale exponent per kv head and dequantizes
    in-register — the wire format never round-trips through a dense pool.
    The caller masks to *full* pages only (``lengths`` floored to a page
    multiple); the newest partial page merges as one extra dense-tail
    split outside (``ops.paged_decode_attention_quant``)."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_stats(m_s, l_s, acc_s)

    pos = _positions(_block_start(page_len, bps), page_len)
    length = lens_ref[pl.program_id(0)]
    for g in range(q_ref.shape[1]):               # static: G kv heads
        k = _dequant_block(k_ref[0, :, g, :], ks_ref[0, 0, g], n_bits)
        v = _dequant_block(v_ref[0, :, g, :], vs_ref[0, 0, g], n_bits)
        _online_softmax_step(q_ref[0, g], k, v, g, pos, length,
                             m_s, l_s, acc_s, zero_masked_p=True)

    @pl.when(j == bps - 1)
    def _flush():
        _flush_stats(o_ref, m_ref, l_ref, m_s, l_s, acc_s)


def paged_attention_quant_kernel(qg: jnp.ndarray, k_codes: jnp.ndarray,
                                 k_scale: jnp.ndarray, v_codes: jnp.ndarray,
                                 v_scale: jnp.ndarray,
                                 page_table: jnp.ndarray,
                                 lengths: jnp.ndarray, *, n_bits: int = 4,
                                 splits: int = 1, interpret: bool = False):
    """qg (B, G, R, D); code pools (P, page_len, G, D) packed log2 codes;
    scale pools (P, G) int32; ``lengths`` must already be floored to full
    pages (the dense tail merges outside).  Returns partial ``(o, m, l)``
    like :func:`paged_attention_kernel`."""
    b, g, r, d = qg.shape
    k_scale = k_scale.reshape(k_scale.shape[0], 1, g)
    v_scale = v_scale.reshape(v_scale.shape[0], 1, g)
    page_len = k_codes.shape[1]
    nb = page_table.shape[1]
    grid, in_specs, out_specs, scratch_shapes, bps = paged_attn_quant_specs(
        b, g, r, d, page_len, nb, splits)

    kern = functools.partial(_paged_attn_quant_kernel, page_len=page_len,
                             bps=bps, n_bits=n_bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=_partials_shape(b, g, r, d, splits),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, qg, k_codes, k_scale, v_codes, v_scale)


# ---------------------------------------------------------------------------
# static-verifier registration (analysis.kernel_rules)
# ---------------------------------------------------------------------------


def make_page_table(lengths, nb: int, page_len: int):
    """The canonical page table of a decode tick: each slot's pages are
    allocated sequentially from page 1 (page 0 is the PR 5 reserved trash
    page), columns past ``ceil(length / page_len)`` stay trash.  Shared by
    the kernel microbench and the audit instantiations so the traffic
    numbers can't drift apart."""
    import numpy as np

    lens = np.asarray(lengths, np.int32)
    table = np.zeros((len(lens), nb), np.int32)
    nxt = 1
    for i, ln in enumerate(lens):
        for j in range(-(-int(ln) // page_len)):
            table[i, j] = nxt
            nxt += 1
    return table


def audit_specs():
    """Registered instantiations for the static kernel verifier.

    Enumerates the audit matrix — the ragged512 bench geometry (the
    gather_saved_frac gate), the serve-smoke geometry the scheduler's tick
    actually compiles (page_len 4, the distinctive 34-page pool), a GQA
    edge case and the qwen2.5-14b-l12 serving cell's geometry — across
    splits and pool dtypes.  Each instantiation hands the verifier the
    SAME BlockSpecs :func:`paged_attn_specs` gives ``pallas_call`` (the
    float kernel's ``ppb`` page operands per pool named ``k_pool.<i>`` /
    ``v_pool.<i>``), plus the concrete scalar-prefetch operands (the
    float kernel's :func:`clamped_walk` of the padded table, or the
    quantized kernel's table; lengths) the index maps dereference;
    ``meta["table"]`` is the page table before padding.
    """
    import numpy as np

    from repro.analysis.pallas_inspect import (KernelInstantiation,
                                               make_operand, scratch_entry)

    cases = [
        # (case name, geometry, splits, pool/q dtype, n_pages)
        ("ragged512.s1", RAGGED512, 1, jnp.float32, None),
        ("ragged512.s4", RAGGED512, 4, jnp.float32, None),
        ("serve_smoke.s1",
         dict(b=4, page_len=4, nb=8, g=1, r=3, d=16,
              lengths=(0, 1, 31, 32)), 1, jnp.float32, 34),
        ("serve_smoke.s2",
         dict(b=4, page_len=4, nb=8, g=1, r=3, d=16,
              lengths=(32, 5, 3, 9)), 2, jnp.bfloat16, 34),
        ("gqa_edge.s2",
         dict(b=2, page_len=8, nb=4, g=3, r=4, d=8,
              lengths=(7, 32)), 2, jnp.bfloat16, None),
        # the qwen2.5-14b-l12 serving cell: 32 slots over a 260-page
        # table, 2,400 pages, bf16
        ("qwen14b_decode.s1",
         dict(b=32, page_len=16, nb=260, g=8, r=5, d=128,
              lengths=QWEN14B_LENGTHS), 1, jnp.bfloat16, 2400),
    ]
    out = []
    for name, geo, splits, dtype, n_pages in cases:
        b, pl_, nb = geo["b"], geo["page_len"], geo["nb"]
        g, r, d = geo["g"], geo["r"], geo["d"]
        lens = np.asarray(geo["lengths"], np.int32)
        if n_pages is None:
            n_pages = 1 + b * nb
        table = make_page_table(lens, nb, pl_)
        assert table.max() < n_pages, (name, table.max(), n_pages)
        # what the kernel reads: the table padded with trash columns to
        # whole blocks of every split, as ops pads it, then clamped
        ppb = block_pages(pl_, nb)
        padded = np.pad(table, ((0, 0), (0, (-nb) % (splits * ppb))))
        walk = np.asarray(clamped_walk(padded, lens, pl_, ppb))
        grid, in_specs, out_specs, scratch, bps = paged_attn_specs(
            b, g, r, d, pl_, walk.shape[1], splits, ppb)
        pool_shape = (n_pages, pl_, g, d)
        inputs = [make_operand("q", (b, g, r, d), dtype, in_specs[0])]
        for k, pool in enumerate(("k_pool", "v_pool")):
            for i in range(ppb):
                inputs.append(make_operand(f"{pool}.{i}", pool_shape, dtype,
                                           in_specs[1 + k * ppb + i]))
        outputs = (
            make_operand("o", (b, splits, g, r, d), jnp.float32,
                         out_specs[0]),
            make_operand("m", (b, splits, g, r), jnp.float32, out_specs[1]),
            make_operand("l", (b, splits, g, r), jnp.float32, out_specs[2]),
        )
        out.append(KernelInstantiation(
            kernel="paged_attention", case=name, grid=grid,
            inputs=tuple(inputs), outputs=outputs,
            scratch=tuple(scratch_entry(s) for s in scratch),
            scalars=(walk, lens),
            meta=dict(page_len=pl_, bps=bps, ppb=ppb, splits=splits,
                      n_pages=n_pages, trash_page=0, table=table,
                      lengths=lens),
        ))

    # quantized-pool variants (ServeScheduler kv_quant=True): same table
    # walk, but the K/V operands are packed log2 code pools + (P, 1, G)
    # scale pools — the audit's byte model makes the compressed-page traffic
    # saving a gated number (page_read_saved_frac).  The kernel is masked
    # to full pages (lengths floored; the dense tail merges outside), but
    # the allocated tail page still streams, so liveness/table rules use
    # the ORIGINAL lengths.
    from repro.core.logquant import code_dtype
    quant_cases = [
        ("ragged512.q4.s2", RAGGED512, 2, 4, None),
        ("serve_smoke.q4.s1",
         dict(b=4, page_len=4, nb=8, g=1, r=3, d=16,
              lengths=(0, 1, 31, 32)), 1, 4, 34),
        ("gqa_edge.q8.s2",
         dict(b=2, page_len=8, nb=4, g=3, r=4, d=8,
              lengths=(7, 32)), 2, 8, None),
    ]
    for name, geo, splits, kv_bits, n_pages in quant_cases:
        b, pl_, nb = geo["b"], geo["page_len"], geo["nb"]
        g, r, d = geo["g"], geo["r"], geo["d"]
        lens = np.asarray(geo["lengths"], np.int32)
        if n_pages is None:
            n_pages = 1 + b * nb
        table = make_page_table(lens, nb, pl_)
        kern_lens = (np.maximum(lens - 1, 0) // pl_ * pl_).astype(np.int32)
        grid, in_specs, out_specs, scratch, bps = paged_attn_quant_specs(
            b, g, r, d, pl_, nb, splits)
        ct = code_dtype(kv_bits)
        pool_shape = (n_pages, pl_, g, d)
        inputs = (
            make_operand("q", (b, g, r, d), jnp.float32, in_specs[0]),
            make_operand("k_pool", pool_shape, ct, in_specs[1]),
            make_operand("k_scale", (n_pages, 1, g), jnp.int32,
                         in_specs[2]),
            make_operand("v_pool", pool_shape, ct, in_specs[3]),
            make_operand("v_scale", (n_pages, 1, g), jnp.int32,
                         in_specs[4]),
        )
        outputs = (
            make_operand("o", (b, splits, g, r, d), jnp.float32,
                         out_specs[0]),
            make_operand("m", (b, splits, g, r), jnp.float32, out_specs[1]),
            make_operand("l", (b, splits, g, r), jnp.float32, out_specs[2]),
        )
        out.append(KernelInstantiation(
            kernel="paged_attention", case=name, grid=grid,
            inputs=inputs, outputs=outputs,
            scratch=tuple(scratch_entry(s) for s in scratch),
            scalars=(table, kern_lens),
            meta=dict(page_len=pl_, bps=bps, splits=splits, n_pages=n_pages,
                      trash_page=0, table=table, lengths=lens,
                      kv_bits=kv_bits),
        ))
    return out
