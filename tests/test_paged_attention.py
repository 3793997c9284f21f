"""Paged-attention kernel harness (ISSUE 6): the fused Pallas decode kernel
(``kernels/paged_attention``) vs the dense-gather masked-einsum oracle, the
split-KV (m, l) partial-softmax merge numerics, and end-to-end scheduler
token parity with the kernel dispatched behind ``attn_kernel``.

Property tests use ``hypothesis`` when installed (``requirements-dev.txt``);
without it the same invariants run over a deterministic seeded lattice, so
``python -m pytest`` stays green on a bare ``jax + pytest`` environment.

Exactness bars (documented here, referenced from DESIGN.md):

* **kernel vs reference, float32**: the kernel reassociates the softmax
  (online (m, l) accumulation page by page) while the oracle computes it
  monolithically, so logits agree to f32 rounding of the reassociation —
  measured max abs error ~2e-7 on the lattice; asserted at
  ``rtol=2e-5, atol=2e-6`` (two orders of headroom).
* **bfloat16 inputs**: both paths accumulate in f32 but round the
  probabilities to bf16 before the PV product (matching the dense path's
  ``p.astype(q.dtype)``), so disagreement is bf16-rounding of nearly-equal
  p's; asserted at ``atol=2e-2``.
* **trash-page isolation / split padding / COW aliasing**: BITWISE.  A
  masked position's weight is ``exp(-1e30 - m)`` which underflows to exact
  0.0 in f32, so trash/junk values multiply by literal zero; an all-masked
  split merges with weight ``exp(-1e30 - M)`` = exact 0.0.  These are
  ``assert_array_equal``, not allclose.
* **scheduler tokens**: kernel path equals the dense-gather scheduler
  token-for-token on every tested seed/arch — same empirical bar as
  chunked-vs-bucketed prefill (reassociated logits make bitwise equality
  a per-seed fact, not a guarantee).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed (the deterministic "
                                "lattice covers the same invariants)")

from repro.configs import get_smoke
from repro.kernels.paged_attention.kernel import block_pages
from repro.kernels.paged_attention.ops import (gather_traffic_counts,
                                               merge_split_softmax,
                                               paged_decode_attention)
from repro.kernels.paged_attention.ref import (NEG_INF,
                                               paged_attention_reference)
from repro.models import init_params
from repro.models.quantize import quantize_model_params
from repro.serving.kvpool import TRASH_PAGE
from repro.serving.scheduler import ServeScheduler

F32_TOL = dict(rtol=2e-5, atol=2e-6)
BF16_TOL = dict(rtol=0.0, atol=2e-2)


def _make_case(rng, *, page_len, nb, g, r, d, lengths, dtype=jnp.float32,
               poison=0.0):
    """Build a pool + per-row page table the way the scheduler lays them
    out: each row's first ``ceil(len/page_len)`` table entries name fresh
    pages, the rest point at the trash page (id 0), whose contents are
    ``poison``."""
    b = len(lengths)
    n_pages = 1 + b * nb
    k = rng.standard_normal((n_pages, page_len, g, d)).astype(np.float32)
    v = rng.standard_normal((n_pages, page_len, g, d)).astype(np.float32)
    k[TRASH_PAGE] = poison
    v[TRASH_PAGE] = poison
    table = np.full((b, nb), TRASH_PAGE, np.int32)
    nxt = 1
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // page_len)):
            table[i, j] = nxt
            nxt += 1
    q = rng.standard_normal((b, 1, g * r, d)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


def _lengths_lattice(page_len, nb):
    """Per-row lengths covering the page-boundary lattice: empty row, one
    token, page_len +/- 1, exact multiples, and the full table."""
    mx = page_len * nb
    cand = [0, 1, page_len - 1, page_len, page_len + 1, 2 * page_len, mx]
    return [ln for ln in dict.fromkeys(cand) if 0 <= ln <= mx]


def _check_parity(rng, *, page_len, nb, g, r, d, splits, dtype=jnp.float32,
                  tol=F32_TOL):
    lengths = _lengths_lattice(page_len, nb)
    q, k, v, table, lens = _make_case(rng, page_len=page_len, nb=nb, g=g,
                                      r=r, d=d, lengths=lengths, dtype=dtype,
                                      poison=1e4)
    out = paged_decode_attention(q, k, v, table, lens, splits=splits)
    ref = paged_attention_reference(q, k, v, table, lens)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live], **tol)
    # length-0 rows (free slots) are finite garbage, never NaN/inf — the
    # scheduler discards them, but a NaN would poison reductions upstream
    assert np.isfinite(np.asarray(out, np.float32)).all()


class TestKernelVsReference:
    """Deterministic parity lattice: page geometry x GQA grouping x splits
    (including splits that do NOT divide the block count, exercising the
    trash-column padding) x dtype, with the trash page poisoned at 1e4."""

    @pytest.mark.parametrize("page_len,nb", [(1, 4), (4, 4), (8, 3)])
    @pytest.mark.parametrize("g,r", [(1, 1), (2, 2), (1, 3)])
    def test_f32_lattice(self, page_len, nb, g, r):
        rng = np.random.default_rng(page_len * 100 + g * 10 + r)
        for splits in (1, 2, 3):
            _check_parity(rng, page_len=page_len, nb=nb, g=g, r=r, d=8,
                          splits=splits)

    def test_bf16_inputs(self):
        rng = np.random.default_rng(42)
        for splits in (1, 2):
            _check_parity(rng, page_len=4, nb=4, g=2, r=2, d=16,
                          splits=splits, dtype=jnp.bfloat16, tol=BF16_TOL)

    def test_gqa_wide_groups(self):
        rng = np.random.default_rng(7)
        _check_parity(rng, page_len=4, nb=2, g=3, r=4, d=16, splits=2)

    @needs_hypothesis
    def test_property_parity(self):
        @settings(max_examples=25, deadline=None)
        @given(page_len=st.integers(1, 8), nb=st.integers(1, 4),
               g=st.integers(1, 3), r=st.integers(1, 4),
               splits=st.integers(1, 4), seed=st.integers(0, 2 ** 16),
               data=st.data())
        def check(page_len, nb, g, r, splits, seed, data):
            mx = page_len * nb
            lengths = data.draw(st.lists(st.integers(0, mx), min_size=1,
                                         max_size=5))
            rng = np.random.default_rng(seed)
            q, k, v, table, lens = _make_case(
                rng, page_len=page_len, nb=nb, g=g, r=r, d=8,
                lengths=lengths, poison=1e4)
            out = paged_decode_attention(q, k, v, table, lens, splits=splits)
            ref = paged_attention_reference(q, k, v, table, lens)
            live = np.asarray(lens) > 0
            np.testing.assert_allclose(np.asarray(out)[live],
                                       np.asarray(ref)[live], **F32_TOL)
            assert np.isfinite(np.asarray(out)).all()
        check()


class TestBlockWalk:
    """The float kernel walks ``block_pages`` pages per grid step, each
    page operand clamped to the row's last live page, and skips blocks
    wholly past the length."""

    def test_block_pages(self):
        assert block_pages(16, 260) == 8        # the qwen2.5-14b-l12 cell
        assert block_pages(16, 4) == 4          # never wider than the table
        assert block_pages(4, 64) == 32
        assert block_pages(256, 16) == 1

    @pytest.mark.parametrize("page_len,nb", [(16, 20), (8, 40)])
    @pytest.mark.parametrize("splits", [1, 2, 3])
    def test_block_boundary_lattice(self, page_len, nb, splits):
        """Lengths on each side of the block boundary, two blocks and the
        full table, with ``nb`` not a multiple of ``ppb`` (8 and 16 pages
        a block here) so the table pads to whole blocks of every split."""
        ppb = block_pages(page_len, nb)
        assert nb % ppb
        blk = ppb * page_len
        lengths = [0, 1, blk - 1, blk, blk + 1, 2 * blk, nb * page_len]
        rng = np.random.default_rng(page_len * 10 + splits)
        q, k, v, table, lens = _make_case(rng, page_len=page_len, nb=nb,
                                          g=2, r=2, d=8, lengths=lengths,
                                          poison=1e4)
        out = np.asarray(paged_decode_attention(q, k, v, table, lens,
                                                splits=splits))
        ref = np.asarray(paged_attention_reference(q, k, v, table, lens))
        live = np.asarray(lens) > 0
        np.testing.assert_allclose(out[live], ref[live], **F32_TOL)
        assert np.isfinite(out).all()

    @pytest.mark.parametrize("splits", [1, 2, 3])
    def test_dead_columns_never_read(self, splits):
        """Table columns past each row's length point at REAL pages full
        of poison (not at the trash page): live rows are bitwise what they
        are with a clean table, so no dead block is read or folded in.  A
        NaN page that were read would turn its zero weight into NaN."""
        page_len, nb = 4, 40                      # 32 pages a block
        lengths = [1, 5, 127, 128, 129, 150]
        rng = np.random.default_rng(31)
        q, k, v, table, lens = _make_case(rng, page_len=page_len, nb=nb,
                                          g=2, r=2, d=8, lengths=lengths)
        base = np.asarray(paged_decode_attention(q, k, v, table, lens,
                                                 splits=splits))
        table = np.asarray(table).copy()
        k, v = np.asarray(k).copy(), np.asarray(v).copy()
        n_pages = k.shape[0]
        for poison in (1e4, -1e4, np.nan):
            kp = np.concatenate([k, np.full_like(k[:nb], poison)])
            vp = np.concatenate([v, np.full_like(v[:nb], poison)])
            stale = table.copy()
            for i, ln in enumerate(lengths):
                n = -(-ln // page_len)
                stale[i, n:] = n_pages + np.arange(nb - n)
            out = np.asarray(paged_decode_attention(
                q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(stale),
                lens, splits=splits))
            np.testing.assert_array_equal(out, base)


class TestTrashPageIsolation:
    """Trash-page contents can never reach the logits: outputs are BITWISE
    identical whatever page 0 holds, because every trash-slot position is
    masked to NEG_INF before the online max and its weight underflows to
    exact 0.0."""

    LENGTHS = [0, 1, 3, 4, 5, 16]

    def _outs(self, poison, splits):
        rng = np.random.default_rng(11)
        q, k, v, table, lens = _make_case(
            rng, page_len=4, nb=4, g=2, r=2, d=8,
            lengths=self.LENGTHS, poison=poison)
        return np.asarray(paged_decode_attention(q, k, v, table, lens,
                                                 splits=splits))

    @pytest.mark.parametrize("splits", [1, 2, 3])
    def test_poison_invisible_bitwise(self, splits):
        """Rows with >= 1 valid token: bitwise independent of trash
        contents.  A length-0 row reads ONLY trash pages — its output is
        poison-dependent garbage by construction, which is fine because
        the scheduler never reads a free slot's logits; the contract for
        those rows is finiteness only (no NaN to poison reductions)."""
        live = np.asarray(self.LENGTHS) > 0
        base = self._outs(0.0, splits)
        for poison in (1e4, -1e4):
            out = self._outs(poison, splits)
            np.testing.assert_array_equal(base[live], out[live])
            assert np.isfinite(out).all()

    def test_cow_aliased_tables(self):
        """Prefix-cache aliasing: rows whose tables share page ids (a radix
        hit refs the donor's pages) read identically to a deep-copied
        table — the kernel walk has no per-row ownership assumption."""
        rng = np.random.default_rng(12)
        q, k, v, table, lens = _make_case(
            rng, page_len=4, nb=4, g=2, r=2, d=8, lengths=[8, 9, 12])
        table = np.asarray(table).copy()
        # rows 1 and 2 alias row 0's first two pages (shared 8-token prefix)
        table[1, :2] = table[0, :2]
        table[2, :2] = table[0, :2]
        aliased = paged_decode_attention(q, k, v, jnp.asarray(table), lens,
                                         splits=2)
        # de-alias: copy the shared pages into fresh slots (what COW would
        # materialize) — bitwise-identical reads
        k2, v2 = np.asarray(k).copy(), np.asarray(v).copy()
        k2 = np.concatenate([k2, k2[table[0, :2]], k2[table[0, :2]]])
        v2 = np.concatenate([v2, v2[table[0, :2]], v2[table[0, :2]]])
        fresh = np.arange(len(k2) - 4, len(k2))
        t2 = table.copy()
        t2[1, :2] = fresh[:2]
        t2[2, :2] = fresh[2:]
        deep = paged_decode_attention(q, jnp.asarray(k2), jnp.asarray(v2),
                                      jnp.asarray(t2), lens, splits=2)
        np.testing.assert_array_equal(np.asarray(aliased), np.asarray(deep))


class TestSplitSoftmaxNumerics:
    """The (m, l) partial-reduction merge vs a monolithic softmax.

    Bar: with the global max subtracted, the merge recombination is the
    same sum the monolithic softmax computes, reassociated per split —
    f32 agreement to ``rtol=2e-5, atol=1e-7`` even at logits of +/-1e4
    (both sides are max-shifted so no exp overflows).  Degenerate cases
    (all-masked split, single valid token) are BITWISE."""

    def _partials(self, s, v, bounds):
        """Per-split online-softmax partials of logits ``s (R, K)`` against
        values ``v (K, D)``, split at ``bounds``."""
        ms, ls, accs = [], [], []
        for lo, hi in bounds:
            blk = s[:, lo:hi]
            m = np.max(blk, axis=1) if hi > lo else np.full(s.shape[0],
                                                            NEG_INF)
            p = np.exp(blk - m[:, None])
            ms.append(m)
            ls.append(p.sum(axis=1))
            accs.append(p @ v[lo:hi])
        return (jnp.asarray(np.stack(ms, 1), jnp.float32),
                jnp.asarray(np.stack(ls, 1), jnp.float32),
                jnp.asarray(np.stack(accs, 1), jnp.float32))

    def test_extreme_logits_match_monolithic(self):
        rng = np.random.default_rng(21)
        r, k_len, d = 4, 24, 8
        s = rng.choice([-1e4, -30.0, -1.0, 0.5, 30.0, 1e4],
                       size=(r, k_len)).astype(np.float32)
        v = rng.standard_normal((k_len, d)).astype(np.float32)
        m, l, acc = self._partials(s, v, [(0, 7), (7, 16), (16, 24)])
        merged = np.asarray(merge_split_softmax(m, l, acc, axis=1))
        mono = (np.exp(s - s.max(1, keepdims=True))
                / np.exp(s - s.max(1, keepdims=True)).sum(1, keepdims=True)
                ) @ v
        np.testing.assert_allclose(merged, mono, rtol=2e-5, atol=1e-7)

    def test_all_masked_split_is_bitwise_absent(self):
        """A split whose every position was masked carries m = NEG_INF and
        arbitrary junk in (l, acc); its merge weight exp(NEG_INF - M)
        underflows to exact 0.0, so the result is BITWISE the merge of the
        remaining splits."""
        rng = np.random.default_rng(22)
        r, k_len, d = 3, 12, 4
        s = rng.standard_normal((r, k_len)).astype(np.float32) * 5
        v = rng.standard_normal((k_len, d)).astype(np.float32)
        m, l, acc = self._partials(s, v, [(0, 6), (6, 12)])
        junk_m = jnp.full((r, 1), NEG_INF, jnp.float32)
        junk_l = jnp.full((r, 1), 123.456, jnp.float32)
        junk_a = jnp.full((r, 1, d), -777.0, jnp.float32)
        with_junk = merge_split_softmax(
            jnp.concatenate([m, junk_m], 1), jnp.concatenate([l, junk_l], 1),
            jnp.concatenate([acc, junk_a], 1), axis=1)
        without = merge_split_softmax(m, l, acc, axis=1)
        np.testing.assert_array_equal(np.asarray(with_junk),
                                      np.asarray(without))

    def test_all_splits_masked_is_finite(self):
        """Every split masked (a free slot's row): m = NEG_INF everywhere.
        The merge max-shifts to 0, so l stays positive and the output is
        finite garbage — never NaN (the scheduler discards these rows)."""
        m = jnp.full((2, 3), NEG_INF, jnp.float32)
        l = jnp.full((2, 3), 4.0, jnp.float32)
        acc = jnp.ones((2, 3, 5), jnp.float32)
        out = np.asarray(merge_split_softmax(m, l, acc, axis=1))
        assert np.isfinite(out).all()

    def test_single_valid_token_is_exact(self):
        """One valid token in one split: softmax collapses to probability
        1.0 exactly (p = exp(0), l = 1), so the output IS that token's
        value row, bitwise — however extreme its logit."""
        d = 6
        rng = np.random.default_rng(23)
        vrow = rng.standard_normal((1, d)).astype(np.float32)
        for logit in (-1e4, 0.0, 1e4):
            m = jnp.asarray([[NEG_INF, logit, NEG_INF]], jnp.float32)
            l = jnp.asarray([[7.0, 1.0, 7.0]], jnp.float32)
            acc = jnp.stack([jnp.full((1, d), 9.0), jnp.asarray(vrow),
                             jnp.full((1, d), -9.0)], 1)
            out = np.asarray(merge_split_softmax(m, l, acc, axis=1))
            np.testing.assert_array_equal(out[:, :], vrow)

    def test_kernel_splits_bitwise_vs_monolithic(self):
        """End-to-end split invariants.  (a) When every VALID page of every
        row lands in split 0 (lengths <= 8 of 16 slots, splits=2), the
        other split is all-masked junk and the output is BITWISE the
        splits=1 output.  (b) When valid pages SPAN splits (splits=4, one
        page per split), the merge reassociates — ``exp(s - m_local) *
        exp(m_local - M)`` vs the online path's running rescale — so the
        bar drops to the f32 reassociation tolerance, same as vs the
        oracle."""
        rng = np.random.default_rng(24)
        q, k, v, table, lens = _make_case(
            rng, page_len=4, nb=4, g=2, r=2, d=8, lengths=[4, 7, 8])
        base = np.asarray(paged_decode_attention(q, k, v, table, lens,
                                                 splits=1))
        out2 = np.asarray(paged_decode_attention(q, k, v, table, lens,
                                                 splits=2))
        np.testing.assert_array_equal(base, out2)
        out4 = np.asarray(paged_decode_attention(q, k, v, table, lens,
                                                 splits=4))
        np.testing.assert_allclose(base, out4, **F32_TOL)
        # row 0 (length 4) has its single valid page alone in split 0 even
        # at splits=4: still bitwise
        np.testing.assert_array_equal(base[0], out4[0])

    @needs_hypothesis
    def test_property_merge_associativity(self):
        @settings(max_examples=50, deadline=None)
        @given(seed=st.integers(0, 2 ** 16), n_splits=st.integers(1, 5),
               k_len=st.integers(1, 32))
        def check(seed, n_splits, k_len):
            rng = np.random.default_rng(seed)
            s = (rng.standard_normal((2, k_len)) * 50).astype(np.float32)
            v = rng.standard_normal((k_len, 4)).astype(np.float32)
            cuts = sorted(rng.integers(0, k_len + 1, size=n_splits - 1))
            bounds = list(zip([0] + list(cuts), list(cuts) + [k_len]))
            m, l, acc = self._partials(s, v, bounds)
            merged = np.asarray(merge_split_softmax(m, l, acc, axis=1))
            e = np.exp(s - s.max(1, keepdims=True))
            mono = (e / e.sum(1, keepdims=True)) @ v
            np.testing.assert_allclose(merged, mono, rtol=2e-5, atol=1e-6)
        check()


class TestGatherTraffic:
    def test_counts(self):
        table = np.zeros((3, 4), np.int32)
        touched, total = gather_traffic_counts(table, np.asarray([0, 1, 9]),
                                               page_len=4)
        assert total == 12.0          # dense gather streams every column
        assert touched == 0 + 1 + 3   # kernel walks only ceil(len/pl)


@pytest.fixture(scope="module")
def smollm_setup():
    cfg = get_smoke("smollm_135m").replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 8, 3, 12, 7, 9)]
    return cfg, params, prompts


def _run_sched(cfg, params, prompts, max_new, **kw):
    kw2 = dict(max_slots=2, max_len=64, buckets=(8, 16), tick_steps=4,
               paged=True, page_len=8, prefix_cache=True)
    kw2.update(kw)
    sched = ServeScheduler(cfg, params, **kw2)
    for p in prompts:
        sched.submit(p, max_new=max_new)
    return [r.tokens for r in sched.run()]


class TestSchedulerKernelParity:
    """Acceptance: the kernel path serves the same tokens as the
    dense-gather scheduler (which ISSUE 5 proved bit-equal to the dense
    slab) — float and quantized, MHA and GQA, prefix cache on."""

    def test_smollm_float_tokens_equal(self, smollm_setup):
        cfg, params, prompts = smollm_setup
        dense = _run_sched(cfg, params, prompts, 7)
        for splits in (1, 2):
            kern = _run_sched(cfg, params, prompts, 7, attn_kernel=True,
                              attn_splits=splits)
            assert dense == kern

    def test_smollm_quant_tokens_equal(self, smollm_setup):
        cfg, params, prompts = smollm_setup
        qparams = quantize_model_params(cfg, params)
        dense = _run_sched(cfg, qparams, prompts, 5, quant="xla")
        kern = _run_sched(cfg, qparams, prompts, 5, quant="xla",
                          attn_kernel=True, attn_splits=2)
        assert dense == kern

    def test_qwen3_gqa_tokens_equal(self):
        cfg = get_smoke("qwen3_32b").replace(dtype=jnp.float32)
        params = init_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (4, 11, 6)]
        dense = _run_sched(cfg, params, prompts, 5)
        kern = _run_sched(cfg, params, prompts, 5, attn_kernel="pallas",
                          attn_splits=2)
        assert dense == kern

    def test_kernel_requires_paged(self, smollm_setup):
        cfg, params, _ = smollm_setup
        with pytest.raises(ValueError, match="requires paged"):
            ServeScheduler(cfg, params, max_slots=2, max_len=64,
                           buckets=(8,), attn_kernel=True)
        with pytest.raises(ValueError, match="attn_splits"):
            ServeScheduler(cfg, params, max_slots=2, max_len=64,
                           buckets=(8,), paged=True, page_len=8,
                           attn_kernel=True, attn_splits=0)
