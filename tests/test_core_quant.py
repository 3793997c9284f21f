"""Unit + property tests for the QeiHaN core quantization math.

Property tests use ``hypothesis`` when it is installed (see
``requirements-dev.txt``); without it the same invariants run over
deterministic seeded sweeps, so ``python -m pytest`` stays green on a bare
``jax + pytest`` environment.
"""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed (deterministic "
                                "fallback cases cover the same invariants)")

from repro.core import (calibrate_act_scale, code_dtype, from_bitplanes,
                        log2_dequantize, log2_quantize, log2_quantize_naive,
                        needed_bits, pack_codes, pack_planes,
                        quantize_weights, quantized_linear_apply,
                        quantized_linear_init, shift_product,
                        shiftadd_matmul_bitplane, shiftadd_matmul_elementwise,
                        shiftadd_matmul_exact, to_bitplanes, unpack_codes,
                        unpack_planes, weight_access_report, zero_sentinel)
from repro.core.logquant import (LogQuantized, negative_fraction,
                                 scale_exponent)

if HAVE_HYPOTHESIS:
    finite_f32 = st.floats(min_value=-1e4, max_value=1e4, width=32,
                           allow_nan=False, allow_infinity=False)


def _seeded_float_batches(n_batches=20, max_size=64):
    """Deterministic stand-in for the hypothesis float-list strategy: mixed
    magnitudes (1e-6..1e3), zeros and sign flips, seeded."""
    rng = np.random.default_rng(1234)
    out = []
    for _ in range(n_batches):
        size = int(rng.integers(1, max_size + 1))
        mag = rng.choice([1e-6, 1e-3, 0.1, 1.0, 30.0, 1e3], size)
        x = (rng.normal(0, 1.0, size) * mag).astype(np.float32)
        x[rng.random(size) < 0.1] = 0.0
        out.append(x)
    return out


# ---------------------------------------------------------------------------
# LOG2 quantization (paper Eqs. 2-4, Fig. 5)
# ---------------------------------------------------------------------------

def _check_comparator_matches_naive(xs):
    x = jnp.asarray(xs, jnp.float32)
    a = log2_quantize(x)
    b = log2_quantize_naive(x)
    np.testing.assert_array_equal(np.asarray(a.exp), np.asarray(b.exp))


def _check_dequant_within_half_octave(xs):
    x = jnp.asarray(xs, jnp.float32)
    q = log2_quantize(x)
    xh = log2_dequantize(q)
    alive = np.asarray(q.exp) != zero_sentinel()
    if not alive.any():
        return
    ratio = np.abs(np.asarray(xh))[alive] / np.abs(np.asarray(x))[alive]
    # round-to-nearest exponent => ratio within [2^-0.5, 2^0.5]
    clipped = np.asarray(q.exp)[alive] == 7
    ok = (ratio >= 2 ** -0.51) & (ratio <= 2 ** 0.51) | clipped
    assert ok.all()


class TestLog2Quant:
    def test_exact_powers_of_two(self):
        x = jnp.asarray([2.0 ** e for e in range(-7, 8)], jnp.float32)
        q = log2_quantize(x)
        assert q.exp.tolist() == list(range(-7, 8))
        assert jnp.all(q.sign == 1)

    def test_zero_and_negatives(self):
        q = log2_quantize(jnp.asarray([0.0, -0.0, -4.0, 4.0], jnp.float32))
        assert q.exp[0] == zero_sentinel() and q.exp[1] == zero_sentinel()
        assert q.exp[2] == 2 and q.sign[2] == -1
        assert q.exp[3] == 2 and q.sign[3] == 1

    def test_small_values_prune(self):
        # anything rounding below -8 prunes to the sentinel
        q = log2_quantize(jnp.asarray([1e-30, 2.0 ** -9, 2.0 ** -20],
                                      jnp.float32))
        assert jnp.all(q.exp == zero_sentinel())

    def test_clip_to_max(self):
        q = log2_quantize(jnp.asarray([1e30, jnp.inf], jnp.float32))
        assert jnp.all(q.exp == 7)

    def test_nan_prunes(self):
        q = log2_quantize(jnp.asarray([jnp.nan], jnp.float32))
        assert q.exp[0] == zero_sentinel()

    def test_sqrt2_boundary(self):
        # below sqrt(2) rounds down, above rounds up; f32(sqrt2) < sqrt2
        lo = np.float32(np.sqrt(2.0)) - np.float32(1e-6)
        hi = np.float32(np.sqrt(2.0)) + np.float32(1e-6)
        q = log2_quantize(jnp.asarray([lo, hi]))
        assert q.exp[0] == 0 and q.exp[1] == 1

    @needs_hypothesis
    def test_comparator_matches_naive_property(self):
        @settings(max_examples=300, deadline=None)
        @given(st.lists(finite_f32, min_size=1, max_size=64))
        def run(xs):
            _check_comparator_matches_naive(xs)
        run()

    def test_comparator_matches_naive_seeded(self):
        for xs in _seeded_float_batches():
            _check_comparator_matches_naive(xs)

    @needs_hypothesis
    def test_dequant_within_half_octave_property(self):
        @settings(max_examples=200, deadline=None)
        @given(st.lists(finite_f32.filter(lambda v: abs(v) > 2 ** -8),
                        min_size=1, max_size=64))
        def run(xs):
            _check_dequant_within_half_octave(xs)
        run()

    def test_dequant_within_half_octave_seeded(self):
        for xs in _seeded_float_batches():
            _check_dequant_within_half_octave(xs)

    def test_pack_unpack_codes(self):
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, 256),
                        jnp.float32)
        q = log2_quantize(x)
        q2 = unpack_codes(pack_codes(q))
        np.testing.assert_array_equal(np.asarray(q.exp), np.asarray(q2.exp))
        np.testing.assert_array_equal(np.asarray(q.sign), np.asarray(q2.sign))

    def test_bf16_f16_inputs(self):
        x = np.random.default_rng(1).normal(0, 1, 128).astype(np.float32)
        for dt in (jnp.bfloat16, jnp.float16):
            q32 = log2_quantize(jnp.asarray(x).astype(dt).astype(jnp.float32))
            qdt = log2_quantize(jnp.asarray(x).astype(dt))
            np.testing.assert_array_equal(np.asarray(q32.exp),
                                          np.asarray(qdt.exp))


def _check_pack_roundtrip_width(xs, n_bits):
    """(exp, sign) -> packed wire code -> (exp, sign) is lossless at every
    encoding width, zero-sentinel and negative entries included."""
    q = log2_quantize(jnp.asarray(xs, jnp.float32), n_bits)
    codes = pack_codes(q, n_bits)
    assert codes.dtype == code_dtype(n_bits)
    q2 = unpack_codes(codes, n_bits)
    np.testing.assert_array_equal(np.asarray(q.exp), np.asarray(q2.exp))
    np.testing.assert_array_equal(np.asarray(q.sign), np.asarray(q2.sign))


class TestCodeWidths:
    """Wire-code round trips across encoding widths (the quantized KV pool
    stores these codes; ISSUE 9).  n_bits=8 is the width whose packed code
    (9 bits with the sign) outgrows int8 — the ``code_dtype`` widening."""

    WIDTHS = (2, 3, 4, 5, 8)

    @pytest.mark.parametrize("n_bits", WIDTHS)
    def test_pack_roundtrip_seeded(self, n_bits):
        for xs in _seeded_float_batches():
            _check_pack_roundtrip_width(xs, n_bits)

    @pytest.mark.parametrize("n_bits", WIDTHS)
    def test_sentinel_and_extremes_roundtrip(self, n_bits):
        # exact zeros (sentinel), +/- of the tiniest/hugest magnitudes, and
        # both clip directions survive the pack
        edge = [0.0, -0.0, 1e-30, -1e-30, 1e30, -1e30, 1.0, -1.0,
                2.0 ** zero_sentinel(n_bits), -(2.0 ** zero_sentinel(n_bits))]
        _check_pack_roundtrip_width(edge, n_bits)
        q = log2_quantize(jnp.asarray(edge, jnp.float32), n_bits)
        assert int(q.exp[0]) == zero_sentinel(n_bits)

    @pytest.mark.parametrize("n_bits", WIDTHS)
    def test_negative_fraction_survives_pack(self, n_bits):
        """The D&S unit's Fig. 2 statistic reads unpacked codes; packing
        must preserve it exactly — negative-heavy batches included."""
        rng = np.random.default_rng(9)
        x = -np.abs(rng.normal(0, 0.3, 128)).astype(np.float32)
        x[:8] = 0.0
        q = log2_quantize(jnp.asarray(x), n_bits)
        q2 = unpack_codes(pack_codes(q, n_bits), n_bits)
        np.testing.assert_array_equal(
            np.asarray(negative_fraction(q, n_bits)),
            np.asarray(negative_fraction(q2, n_bits)))

    @needs_hypothesis
    def test_pack_roundtrip_property(self):
        @settings(max_examples=150, deadline=None)
        @given(n_bits=st.sampled_from(self.WIDTHS),
               xs=st.lists(finite_f32, min_size=1, max_size=64))
        def run(n_bits, xs):
            _check_pack_roundtrip_width(xs, n_bits)
        run()

    def test_n_bits_property_reports_actual_width(self):
        """LogQuantized.n_bits: the smallest width whose exponent range
        (sentinel included) covers the stored exponents."""
        def q(exps):
            e = jnp.asarray(exps, jnp.int8)
            return LogQuantized(exp=e, sign=jnp.ones_like(e))
        assert q([0, 1, -1]).n_bits == 2
        assert q([-2]).n_bits == 2            # exactly the 2-bit sentinel
        assert q([2]).n_bits == 3             # above the 2-bit max of 1
        assert q([-3]).n_bits == 3
        assert q([7]).n_bits == 4
        assert q([-8, 7]).n_bits == 4
        assert q([8]).n_bits == 5
        assert q([127]).n_bits == 8
        assert q([-128]).n_bits == 8
        assert q([]).n_bits == 2              # empty: smallest encoding
        for n in (2, 3, 4, 5, 8):
            got = log2_quantize(jnp.asarray(_seeded_float_batches(1)[0]), n)
            assert got.n_bits <= n

    def test_code_dtype_widening(self):
        assert code_dtype(2) == jnp.int8 and code_dtype(7) == jnp.int8
        assert code_dtype(8) == jnp.int16


class TestScaleExponent:
    def test_power_of_two_scale(self):
        x = jnp.asarray([[0.75, -3.0, 0.0], [2.0 ** -9, 0.0, 0.0],
                         [0.0, 0.0, 0.0]], jnp.float32)
        se = scale_exponent(x, axis=-1)
        assert se.tolist() == [1, -9, 0]      # floor(log2 max|x|); zeros -> 0
        assert se.dtype == jnp.int32

    def test_scaled_quantize_is_idempotent(self):
        """The KV-page rewrite invariant at the core level: dividing by the
        power-of-two scale then log2-quantizing an already-dequantized
        value reproduces the exponent exactly (mantissa field 0 sits below
        the sqrt(2) comparator threshold)."""
        rng = np.random.default_rng(13)
        x = (rng.normal(0, 2.0, 256) * rng.choice([1e-3, 1.0, 1e2], 256)
             ).astype(np.float32)
        se = scale_exponent(jnp.asarray(x), axis=-1, keepdims=True)
        inv = jnp.exp2(-se.astype(jnp.float32))
        q1 = log2_quantize(jnp.asarray(x) * inv)
        xh = log2_dequantize(q1) * jnp.exp2(se.astype(jnp.float32))
        q2 = log2_quantize(xh * inv)
        np.testing.assert_array_equal(np.asarray(q1.exp), np.asarray(q2.exp))


# ---------------------------------------------------------------------------
# bit-planes (paper §IV-B)
# ---------------------------------------------------------------------------

def _check_roundtrip(ws):
    q = jnp.asarray(ws, jnp.int8)
    planes = to_bitplanes(q)
    np.testing.assert_array_equal(np.asarray(from_bitplanes(planes)),
                                  np.asarray(q, np.int32))


def _check_dropping_low_planes_is_shift(w, k):
    """The paper's core identity: floor(w / 2^k) uses only planes >= k."""
    planes = to_bitplanes(jnp.asarray([w], jnp.int8))
    masked = planes.at[:k].set(0)
    got = int(from_bitplanes(masked)[0]) >> k         # shift of masked value
    assert got == w >> k


class TestBitplanes:
    @needs_hypothesis
    def test_roundtrip_property(self):
        @settings(max_examples=100, deadline=None)
        @given(st.lists(st.integers(-127, 127), min_size=1, max_size=128))
        def run(ws):
            _check_roundtrip(ws)
        run()

    def test_roundtrip_seeded(self):
        rng = np.random.default_rng(5)
        for _ in range(10):
            _check_roundtrip(rng.integers(-127, 128,
                                          rng.integers(1, 129)).tolist())
        _check_roundtrip(list(range(-127, 128)))      # exhaustive int8 range

    def test_pack_roundtrip(self):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.integers(-127, 128, (64, 32)), jnp.int8)
        planes = to_bitplanes(q)
        packed = pack_planes(planes, axis=0)
        assert packed.shape == (8, 8, 32)
        np.testing.assert_array_equal(np.asarray(unpack_planes(packed, axis=0)),
                                      np.asarray(planes))

    @needs_hypothesis
    def test_dropping_low_planes_property(self):
        @settings(max_examples=100, deadline=None)
        @given(st.integers(-127, 127), st.integers(1, 7))
        def run(w, k):
            _check_dropping_low_planes_is_shift(w, k)
        run()

    def test_dropping_low_planes_exhaustive(self):
        for w in range(-127, 128):
            for k in range(1, 8):
                _check_dropping_low_planes_is_shift(w, k)


# ---------------------------------------------------------------------------
# shift-add matmul (paper Eq. 5): three forms agree exactly
# ---------------------------------------------------------------------------

def _check_shift_product(w, e):
    q = LogQuantized(exp=jnp.asarray([e], jnp.int8),
                     sign=jnp.asarray([1], jnp.int8))
    got = int(shift_product(jnp.asarray([w], jnp.int8), q)[0])
    if e == -8:
        assert got == 0
    elif e >= 0:
        assert got == w * (2 ** e)
    else:
        assert got == w >> (-e)


class TestShiftAdd:
    def _rand(self, m, k, n, seed=0, zero_frac=0.1, scale=0.5):
        rng = np.random.default_rng(seed)
        x = rng.normal(0, scale, (m, k)).astype(np.float32)
        x[rng.random((m, k)) < zero_frac] = 0.0
        q = log2_quantize(jnp.asarray(x))
        w = quantize_weights(
            jnp.asarray(rng.normal(0, 0.1, (k, n)).astype(np.float32)),
            channel_axis=-1)
        return q, w

    @pytest.mark.parametrize("m,k,n", [(4, 16, 8), (3, 100, 17), (16, 64, 64)])
    def test_bitplane_equals_elementwise(self, m, k, n):
        q, w = self._rand(m, k, n, seed=m * k + n)
        y0 = shiftadd_matmul_elementwise(q, w.q)
        y1 = shiftadd_matmul_bitplane(q, to_bitplanes(w.q))
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))

    def test_truncation_error_bounded_per_term(self):
        q, w = self._rand(8, 128, 16, seed=7)
        y_t = shiftadd_matmul_elementwise(q, w.q).astype(jnp.float32)
        y_e = shiftadd_matmul_exact(q, w.q)
        # floor() loses < 1 per contributing term
        assert float(jnp.max(jnp.abs(y_t - y_e))) < 128

    @needs_hypothesis
    def test_shift_product_property(self):
        @settings(max_examples=50, deadline=None)
        @given(st.integers(-127, 127), st.integers(-8, 7))
        def run(w, e):
            _check_shift_product(w, e)
        run()

    def test_shift_product_exhaustive(self):
        for w in (-127, -64, -3, -1, 0, 1, 3, 64, 127):
            for e in range(-8, 8):
                _check_shift_product(w, e)

    def test_quantized_linear_error(self):
        rng = np.random.default_rng(3)
        x = rng.normal(0, 1.0, (8, 256)).astype(np.float32)
        w = rng.normal(0, 0.05, (256, 64)).astype(np.float32)
        p = quantized_linear_init(jnp.asarray(w),
                                  act_scale=calibrate_act_scale(jnp.asarray(x)))
        y = np.asarray(quantized_linear_apply(p, jnp.asarray(x)))
        ref = x @ w
        rel = np.abs(y - ref).mean() / (np.abs(ref).mean() + 1e-9)
        assert rel < 0.25        # LOG2-4bit acts x INT8 weights, no retrain

    def test_backends_agree_exactly(self):
        """The pallas kernel (interpret off-TPU) and the jnp bit-plane form
        compute the identical int32 result through the layer API."""
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.normal(0, 0.5, (4, 96)).astype(np.float32))
        w = jnp.asarray(rng.normal(0, 0.05, (96, 40)).astype(np.float32))
        p = quantized_linear_init(w)
        y_xla = quantized_linear_apply(p, x, backend="xla")
        y_pl = quantized_linear_apply(p, x, backend="pallas")
        np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(y_pl))


# ---------------------------------------------------------------------------
# memory-access model (paper Fig. 3)
# ---------------------------------------------------------------------------

def _check_savings_bounds(xs):
    q = log2_quantize(jnp.asarray(xs, jnp.float32))
    rep = weight_access_report(q)
    assert -1e-6 <= float(rep.savings_element) <= 1.0
    assert float(rep.element_bits) <= float(rep.baseline_bits)


class TestAccessModel:
    def test_needed_bits(self):
        e = jnp.asarray([-8, -7, -3, -1, 0, 3, 7], jnp.int8)
        nb = needed_bits(e)
        assert nb.tolist() == [0, 1, 5, 7, 8, 8, 8]

    def test_all_negative_saves(self):
        q = log2_quantize(jnp.full((1024,), 0.04, jnp.float32))  # exp ~ -5
        rep = weight_access_report(q)
        assert 0.3 < float(rep.savings_element) < 0.8
        assert float(rep.savings_tile) <= float(rep.savings_element) + 1e-6

    def test_positive_exponents_save_nothing(self):
        q = log2_quantize(jnp.full((512,), 8.0, jnp.float32))
        rep = weight_access_report(q)
        assert float(rep.savings_element) == 0.0

    @needs_hypothesis
    def test_savings_bounds_property(self):
        @settings(max_examples=50, deadline=None)
        @given(st.lists(finite_f32, min_size=8, max_size=512))
        def run(xs):
            _check_savings_bounds(xs)
        run()

    def test_savings_bounds_seeded(self):
        for xs in _seeded_float_batches():
            if len(xs) >= 8:
                _check_savings_bounds(xs)
