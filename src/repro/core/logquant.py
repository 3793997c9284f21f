"""LOG2 (logarithmic base-2) activation quantization — QeiHaN paper Eqs. 2-4.

Paper mapping (arXiv 2310.18181; DESIGN.md "Paper ↔ code map"): this module
is the paper's *log2 activation quantization* — §II's observation that
FC/CONV activations concentrate in (-1, 1) and so quantize to powers of two
with mostly NEGATIVE exponents (Fig. 2), encoded via Eqs. 2-4 with the
Fig. 5 single-comparator rounding circuit.  Those negative exponents are
what the weight side (``core/bitplane.py``, §IV-B) turns into skipped
memory accesses; the Pallas realization of this quantizer is
``kernels/log2quant/``.

Implements two bit-identical paths:

* :func:`log2_quantize` — production path.  Extracts the IEEE-754 exponent
  field and applies the paper's single-comparator rounding trick (Fig. 5 /
  Eqs. 6-7): ``Round(log2|x|) = e + (m >= sqrt(2))`` with mantissa
  ``m in [1, 2)``.  Pure integer bit-twiddling; exact for every finite input.
* :func:`log2_quantize_naive` — direct ``Round(log2|x|))`` in floating point.
  Used only as a cross-check; may differ from the comparator path by 1 at
  values whose ``log2`` lands within float error of ``.5`` (measure-zero set;
  the comparator path is the specification).

Encoding (n-bit exponent, default n=4):

* exponents live in ``[-(2^(n-1)) + 1, 2^(n-1) - 1]`` (e.g. ``[-7, 7]``),
* the minimum code ``-(2^(n-1))`` (e.g. ``-8``) is the **zero sentinel**:
  exact zeros and activations whose rounded exponent clips below the range
  are pruned to it (paper: "all small activations are effectively pruned"),
* sign is carried separately (paper: "an extra bit for the sign").

A quantized activation therefore decodes as ``sign * 2^exp`` with the
sentinel decoding to ``0``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LogQuantized",
    "zero_sentinel",
    "code_dtype",
    "log2_quantize",
    "log2_quantize_naive",
    "log2_dequantize",
    "pack_codes",
    "unpack_codes",
    "scale_exponent",
    "quantize_page_codes",
    "dequantize_page_codes",
    "negative_fraction",
    "pruned_fraction",
]

# Mantissa-field threshold for the sqrt(2) comparator on a float32 mantissa
# (23 fraction bits).  m >= sqrt(2)  <=>  M >= _SQRT2_M_F32 where
# m = 1 + M / 2^23.  sqrt(2) is irrational so equality never occurs for a
# finite float; we use the first representable mantissa above sqrt(2).
_SQRT2_M_F32 = int(np.floor((np.sqrt(np.float64(2.0)) - 1.0) * (1 << 23))) + 1


class LogQuantized(NamedTuple):
    """LOG2-quantized activations: ``value = sign * 2^exp`` (sentinel -> 0)."""

    exp: jnp.ndarray   # int8 exponents in [-(2^(n-1)), 2^(n-1)-1]
    sign: jnp.ndarray  # int8 in {-1, +1}

    @property
    def n_bits(self) -> int:
        """Smallest encoding width whose exponent range (including the zero
        sentinel ``-(2^(n-1))``) holds every stored exponent."""
        if self.exp.size == 0:
            return 2
        lo = int(jnp.min(self.exp))
        hi = int(jnp.max(self.exp))
        n = 2
        while -(1 << (n - 1)) > lo or (1 << (n - 1)) - 1 < hi:
            n += 1
        return n


def zero_sentinel(n_bits: int = 4) -> int:
    """The exponent code that represents a pruned/zero activation."""
    return -(1 << (n_bits - 1))


def _exp_mantissa_fields(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Raw IEEE-754 exponent/mantissa fields of ``x`` viewed as float32.

    bf16/f16 inputs are first cast to float32 — an exact embedding, so the
    comparator semantics are unchanged.
    """
    xf = x.astype(jnp.float32)
    bits = jnp.asarray(xf).view(jnp.uint32)
    exp_field = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    man_field = (bits & jnp.uint32(0x7FFFFF)).astype(jnp.int32)
    return exp_field, man_field


def log2_quantize(x: jnp.ndarray, n_bits: int = 4) -> LogQuantized:
    """Paper Eqs. 2-4 via the Fig. 5 comparator circuit. Bit-exact.

    ``Round(log2|x|) = e + (m >= sqrt(2))`` where ``|x| = m * 2^e``,
    ``m in [1, 2)``; then clip to ``[-(2^(n-1)), 2^(n-1)-1]`` with the lower
    clip collapsing onto the zero sentinel (pruning).  Subnormals (exponent
    field 0) are far below any representable 4-bit exponent and prune; NaN is
    pruned; +/-Inf clips to the max exponent.
    """
    exp_field, man_field = _exp_mantissa_fields(x)
    sentinel = zero_sentinel(n_bits)
    emax = (1 << (n_bits - 1)) - 1

    unbiased = exp_field - 127
    rounded = unbiased + (man_field >= _SQRT2_M_F32).astype(jnp.int32)

    is_subnormal_or_zero = exp_field == 0
    is_nonfinite = exp_field == 0xFF
    is_nan = is_nonfinite & (man_field != 0)

    e = jnp.clip(rounded, sentinel, emax)
    # sentinel means "pruned to zero"; anything clipping to it from below,
    # plus exact zeros/subnormals/NaNs, prunes.  Note the clip already maps
    # rounded <= sentinel onto the sentinel; we only force the special cases.
    e = jnp.where(is_subnormal_or_zero | is_nan, sentinel, e)
    e = jnp.where(is_nonfinite & ~is_nan, emax, e)

    sign = jnp.where(x < 0, jnp.int8(-1), jnp.int8(1))
    return LogQuantized(exp=e.astype(jnp.int8), sign=sign)


def log2_quantize_naive(x: jnp.ndarray, n_bits: int = 4) -> LogQuantized:
    """Direct float evaluation of Eq. 3 (cross-check only, not the spec)."""
    sentinel = zero_sentinel(n_bits)
    emax = (1 << (n_bits - 1)) - 1
    absx = jnp.abs(x.astype(jnp.float32))
    # round-half-up on the log, matching `e + (m >= sqrt(2))`.
    raw = jnp.floor(jnp.log2(absx) + 0.5)
    e = jnp.clip(raw, sentinel, emax)
    e = jnp.where((absx == 0) | jnp.isnan(x.astype(jnp.float32)), sentinel, e)
    sign = jnp.where(x < 0, jnp.int8(-1), jnp.int8(1))
    return LogQuantized(exp=e.astype(jnp.int8), sign=sign)


def log2_dequantize(q: LogQuantized, n_bits: int = 4,
                    dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    """``sign * 2^exp`` with the sentinel decoding to exactly 0."""
    sentinel = zero_sentinel(n_bits)
    mag = jnp.exp2(q.exp.astype(jnp.float32))
    val = q.sign.astype(jnp.float32) * mag
    return jnp.where(q.exp == sentinel, 0.0, val).astype(dtype)


def code_dtype(n_bits: int = 4):
    """Container dtype of the packed wire code.

    ``code = exp*2 + sign`` needs ``n_bits + 1`` bits, so int8 holds every
    width up to 7 exponent bits; the 8-bit encoding (exp in [-128, 127])
    widens to int16.
    """
    return jnp.int16 if n_bits >= 8 else jnp.int8


def pack_codes(q: LogQuantized, n_bits: int = 4) -> jnp.ndarray:
    """Pack (exp, sign) into a single code: ``code = exp*2 + (sign<0)``.

    This is the (n_bits+1)-bit (exponent + sign) wire format the PE sends to
    the D&S unit; used by the access model to count activation traffic.
    """
    ct = code_dtype(n_bits)
    neg = (q.sign < 0).astype(ct)
    return (q.exp.astype(ct) << 1) | neg


def unpack_codes(codes: jnp.ndarray, n_bits: int = 4) -> LogQuantized:
    # arithmetic shift keeps the exponent's sign; every width's exponent
    # range fits int8 (the widest, n_bits=8, spans [-128, 127])
    exp = (codes >> 1).astype(jnp.int8)
    sign = jnp.where((codes & 1) != 0, jnp.int8(-1), jnp.int8(1))
    return LogQuantized(exp=exp, sign=sign)


def scale_exponent(x: jnp.ndarray, axis=-1, keepdims: bool = False
                   ) -> jnp.ndarray:
    """Power-of-two row scale: ``floor(log2(max|x|))`` over ``axis`` (int32).

    Zero/subnormal rows scale by 2^0.  A power-of-two scale makes the
    scaled quantize *idempotent*: ``x / 2^se`` of an already-dequantized
    value is again an exact power of two, whose mantissa field is 0 — below
    the sqrt(2) comparator threshold — so requantizing under the same scale
    reproduces the codes bit-for-bit (the quantized KV pool's rewrite
    invariant, DESIGN.md §Quantized KV pages).
    """
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=keepdims)
    exp_field, _ = _exp_mantissa_fields(m)
    return jnp.where(exp_field == 0, 0, exp_field - 127)


def quantize_page_codes(x: jnp.ndarray, scale_exp: jnp.ndarray,
                        n_bits: int = 4) -> jnp.ndarray:
    """LOG2-quantize ``x / 2^scale_exp`` and pack to wire codes.

    ``scale_exp`` (int32) broadcasts against ``x``; scaling by an exact
    power of two never perturbs the mantissa, so the comparator rounding is
    applied to the true scaled magnitudes.  Pruned values get the canonical
    positive-sign sentinel code — they decode to +0.0, and requantizing
    +0.0 must reproduce the same byte (the rewrite invariant).
    """
    scaled = x.astype(jnp.float32) * jnp.exp2(-scale_exp.astype(jnp.float32))
    q = log2_quantize(scaled, n_bits)
    sign = jnp.where(q.exp == zero_sentinel(n_bits), jnp.int8(1), q.sign)
    return pack_codes(LogQuantized(exp=q.exp, sign=sign), n_bits)


def pow2_exact(e: jnp.ndarray) -> jnp.ndarray:
    """``2^e`` in f32 for int32 ``e`` in the normal range [-126, 127],
    built from the exponent field: exact on every backend, where ``exp2``
    may round, or flush ``2^-126`` to zero."""
    return jax.lax.bitcast_convert_type((e.astype(jnp.int32) + 127) << 23,
                                        jnp.float32)


def dequantize_page_codes(codes: jnp.ndarray, scale_exp: jnp.ndarray,
                          n_bits: int = 4,
                          dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    """``sign * 2^(exp + scale_exp)`` with the sentinel decoding to 0.

    The summed exponent is clamped to the f32 normal range so garbage
    scales (trash-page contents) decode to large-but-finite values —
    downstream masking then erases them without Inf/NaN contamination.
    """
    q = unpack_codes(codes, n_bits)
    sentinel = zero_sentinel(n_bits)
    e = jnp.clip(q.exp.astype(jnp.int32) + scale_exp.astype(jnp.int32),
                 -126, 127)
    val = q.sign.astype(jnp.float32) * pow2_exact(e)
    return jnp.where(q.exp == sentinel, 0.0, val).astype(dtype)


def negative_fraction(q: LogQuantized, n_bits: int = 4) -> jnp.ndarray:
    """Fraction of *non-pruned* activations with negative exponent (Fig. 2)."""
    sentinel = zero_sentinel(n_bits)
    alive = q.exp != sentinel
    neg = alive & (q.exp < 0)
    denom = jnp.maximum(jnp.sum(alive), 1)
    return jnp.sum(neg) / denom


def pruned_fraction(q: LogQuantized, n_bits: int = 4) -> jnp.ndarray:
    """Fraction of activations pruned to zero (sentinel) — paper §VI-B."""
    sentinel = zero_sentinel(n_bits)
    return jnp.mean((q.exp == sentinel).astype(jnp.float32))
