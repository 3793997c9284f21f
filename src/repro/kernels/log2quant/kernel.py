"""Pallas kernel: LOG2 activation quantization (paper Fig. 5, Eqs. 6-7).

Paper mapping (arXiv 2310.18181; DESIGN.md "Paper ↔ code map"): the
kernel-side twin of ``core/logquant.py`` — the paper's §II log2 activation
quantization, evaluated as the Fig. 5 comparator circuit (Eqs. 6-7 fold
the Eq. 3 rounding into one exponent-field add + mantissa compare).

Elementwise over a 2D tensor, tiled ``(block_m, block_n)`` in VMEM.  The body
is the same comparator circuit as ``core.logquant.log2_quantize``: IEEE-754
exponent-field extraction plus one mantissa-vs-sqrt(2) compare — no
transcendental evaluation, so VPU-only, fully vectorized, and bit-exact.

VMEM budget at the default (256, 512) f32 block: in 512 KiB + two int8 outs
128 KiB each -> well under a v5e core's ~16 MiB VMEM with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SQRT2_M_F32 = 3474676  # floor((sqrt(2)-1) * 2^23) + 1, see core.logquant


def _log2quant_kernel(x_ref, exp_ref, sign_ref, *, n_bits: int):
    x = x_ref[...].astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    exp_field = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    man_field = (bits & jnp.uint32(0x7FFFFF)).astype(jnp.int32)

    sentinel = -(1 << (n_bits - 1))
    emax = (1 << (n_bits - 1)) - 1

    rounded = exp_field - 127 + (man_field >= _SQRT2_M_F32).astype(jnp.int32)
    e = jnp.clip(rounded, sentinel, emax)

    is_sub_or_zero = exp_field == 0
    is_nonfinite = exp_field == 0xFF
    is_nan = is_nonfinite & (man_field != 0)
    e = jnp.where(is_sub_or_zero | is_nan, sentinel, e)
    e = jnp.where(is_nonfinite & ~is_nan, emax, e)

    exp_ref[...] = e.astype(jnp.int8)
    # select in int32, narrow at the store: Mosaic cannot relayout an i1
    # mask into the int8 (32, 128) tiling an int8 select would need
    sign_ref[...] = jnp.where(x < 0, -1, 1).astype(jnp.int8)


def log2quant_specs(m: int, n: int, block_m: int, block_n: int):
    """Grid + BlockSpec shared by :func:`log2_quantize_kernel` and the
    static verifier's ``audit_specs()`` (one spec serves input and both
    outputs — the quantizer is a pure elementwise map)."""
    grid = (m // block_m, n // block_n)
    spec = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    return grid, spec


def log2_quantize_kernel(x: jnp.ndarray, *, n_bits: int = 4,
                         block_m: int = 256, block_n: int = 512,
                         interpret: bool = False):
    """x: f32/bf16 ``(M, N)`` (pre-padded to block multiples) -> (exp, sign)."""
    m, n = x.shape
    grid, spec = log2quant_specs(m, n, block_m, block_n)
    return pl.pallas_call(
        functools.partial(_log2quant_kernel, n_bits=n_bits),
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((m, n), jnp.int8),
        ],
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# static-verifier registration (analysis.kernel_rules)
# ---------------------------------------------------------------------------


def audit_specs():
    """Registered instantiations for the static kernel verifier: the
    default decode-path tiling in f32 and bf16, plus a single-block edge
    case.  No scalar prefetch, no scratch — the audit mostly proves the
    tiling divides and prices the VMEM/HBM footprint."""
    from repro.analysis.pallas_inspect import (KernelInstantiation,
                                               make_operand)

    cases = [
        ("decode_f32.b256x512", 512, 1024, 256, 512, jnp.float32),
        ("decode_bf16.b256x512", 1024, 512, 256, 512, jnp.bfloat16),
        ("single_block.b128x128", 128, 128, 128, 128, jnp.float32),
    ]
    out = []
    for name, m, n, bm, bn, dtype in cases:
        grid, spec = log2quant_specs(m, n, bm, bn)
        out.append(KernelInstantiation(
            kernel="log2quant", case=name, grid=grid,
            inputs=(make_operand("x", (m, n), dtype, spec),),
            outputs=(
                make_operand("exp", (m, n), jnp.int8, spec),
                make_operand("sign", (m, n), jnp.int8, spec),
            ),
            scratch=(),
            scalars=(),
            meta={},
        ))
    return out
