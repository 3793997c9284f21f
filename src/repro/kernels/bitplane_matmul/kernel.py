"""Pallas kernel: QeiHaN bit-plane shift-add matmul with plane skipping.

Paper mapping (arXiv 2310.18181; DESIGN.md "Paper ↔ code map"): TPU-native
realization of the paper's §IV — the D&S unit's Eq. 5 shift-add
(``core/shiftadd.py``) fused with the §IV-B *implicit bit-shift weight
access*: the scalar-prefetched skip table below is the vault controller
deciding, per tile, which weight bit-planes a negative log2 activation
exponent (§II, Eqs. 2-4) makes it skip.  Computes, exactly in integers,

    y[m, n] = sum_k  sign[m,k] * ArithShift(w[k,n], exp[m,k])

where ``ArithShift(w, e) = w << e`` for ``e >= 0`` and the *truncating*
``floor(w / 2^|e|)`` for ``e < 0`` (the sentinel exponent contributes 0).

Regrouping (see ``core.shiftadd``): ``y = sum_b sgn_b * (A_b @ P_b)`` with
``P_b`` the {0,1} bit-plane of the int8 weights and
``A_b[m,k] = sign * 2^(b + exp)`` wherever ``b + exp >= 0``, else 0.  Each
per-plane, per-K-block partial product is bounded by ``bk * 2^14 < 2^24`` so
an f32 MXU matmul is exact; accumulation across planes/K-blocks happens in an
int32 VMEM scratch.

The paper's memory-access saving appears here as **plane skipping**: a
scalar-prefetched table ``min_plane[mi, ki]`` holds the smallest plane index
any activation in tile ``(mi, ki)`` can touch (``max(0, -max_e)``, or 8 if
the tile is fully pruned).  Planes ``b < min_plane`` are skipped with
``@pl.when`` — on hardware the corresponding weight-plane tiles are never
read out of VMEM and the MXU issues nothing; the HBM-traffic image of the
skip is accounted by ``core.access_model.weight_access_report`` (granularity
='tile') and, for the ASIC, by ``simulator/``.

Grid: ``(M/bm, N/bn, K/bk)``, K innermost (accumulator-friendly).
VMEM at defaults (bm=bk=bn=128): planes block 8*128*128 B = 128 KiB,
exp/sign blocks 2*16 KiB, acc 64 KiB, A_b temporaries ~64 KiB -> ~0.3 MiB,
leaving headroom to raise bn/bk to 512 on real v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WEIGHT_BITS = 8


def _bitplane_matmul_kernel(min_plane_ref,          # scalar prefetch (Mb, Kb)
                            exp_ref, sign_ref,       # (bm, bk) int8
                            planes_ref,              # (8, bk, bn) uint8
                            out_ref,                 # (bm, bn) int32
                            acc_ref,                 # VMEM scratch (bm, bn) int32
                            *, bits: int, n_bits: int, k_blocks: int):
    mi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sentinel = -(1 << (n_bits - 1))
    e = exp_ref[...].astype(jnp.int32)
    s = sign_ref[...].astype(jnp.int32)
    alive = e != sentinel

    min_plane = min_plane_ref[mi, ki]

    for b in range(bits):                          # static unroll: 8 planes
        @pl.when(b >= min_plane)
        def _plane(b=b):
            sh = b + e
            # A_b = sign * 2^(b+e) where contributing; exact powers of two in f32.
            a_b = jnp.where(alive & (sh >= 0),
                            (s << jnp.clip(sh, 0, 14)).astype(jnp.float32),
                            0.0)
            # Mosaic has no direct uint8 -> f32 convert; go through int32
            p_b = planes_ref[b].astype(jnp.int32).astype(jnp.float32)
            term = jax.lax.dot_general(
                a_b, p_b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ti = term.astype(jnp.int32)
            if b == bits - 1:
                ti = -ti                            # two's-complement sign plane
            acc_ref[...] += ti

    @pl.when(ki == k_blocks - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def bitplane_specs(m: int, k: int, n: int, bits: int,
                   block_m: int, block_n: int, block_k: int):
    """Grid + BlockSpecs shared by :func:`bitplane_matmul_kernel` and the
    static verifier's ``audit_specs()``."""
    grid = (m // block_m, n // block_n, k // block_k)
    in_specs = [
        # index maps receive the scalar-prefetch ref as a trailing arg
        pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, mp: (mi, ki)),
        pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, mp: (mi, ki)),
        pl.BlockSpec((bits, block_k, block_n),
                     lambda mi, ni, ki, mp: (0, ki, ni)),
    ]
    out_specs = pl.BlockSpec((block_m, block_n),
                             lambda mi, ni, ki, mp: (mi, ni))
    scratch_shapes = [pltpu.VMEM((block_m, block_n), jnp.int32)]
    return grid, in_specs, out_specs, scratch_shapes


def bitplane_matmul_kernel(exp: jnp.ndarray, sign: jnp.ndarray,
                           planes: jnp.ndarray, min_plane: jnp.ndarray,
                           *, n_bits: int = 4,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """Pre-padded inputs: exp/sign (M, K) int8, planes (8, K, N) uint8,
    min_plane (M/bm, K/bk) int32. Returns int32 (M, N)."""
    m, k = exp.shape
    bits, k2, n = planes.shape
    assert k2 == k, (k2, k)
    grid, in_specs, out_specs, scratch_shapes = bitplane_specs(
        m, k, n, bits, block_m, block_n, block_k)

    kern = functools.partial(_bitplane_matmul_kernel, bits=bits,
                             n_bits=n_bits, k_blocks=grid[2])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(min_plane, exp, sign, planes)


# ---------------------------------------------------------------------------
# static-verifier registration (analysis.kernel_rules)
# ---------------------------------------------------------------------------


def audit_specs():
    """Registered instantiations for the static kernel verifier.

    Three geometries: the canonical sigma-1.0 activation stream at the
    default 128 blocks (the traffic-model gate), the same stream re-tiled
    at 64 blocks (VMEM scaling), and a half-pruned stream exercising the
    fully-skipped ``min_plane == bits`` branch.  The ``min_plane`` skip
    table is built by the SAME ``ops._skip_table`` the runtime wrapper
    uses, so static and measured plane traffic share one source of truth.
    """
    import numpy as np

    from repro.analysis.pallas_inspect import (KernelInstantiation,
                                               make_operand, scratch_entry)
    from repro.kernels.bitplane_matmul.ops import (_skip_table,
                                                  canonical_logquant)

    n_bits = 4
    sentinel = -(1 << (n_bits - 1))
    cases = []

    exp_c, sign_c = canonical_logquant((256, 4096), sigma=1.0, seed=2,
                                       n_bits=n_bits)
    cases.append(("canon_s1.b128", exp_c, sign_c, 512, 128, 128, 128))
    cases.append(("canon_s1.b64", exp_c, sign_c, 512, 64, 64, 64))

    exp_p, sign_p = canonical_logquant((128, 512), sigma=0.25, seed=3,
                                       n_bits=n_bits)
    exp_p = np.array(exp_p)
    exp_p[:, :256] = sentinel              # half the K range fully pruned
    cases.append(("pruned_half.b128", exp_p, sign_p, 256, 128, 128, 128))

    out = []
    for name, exp, sign, n, bm, bn, bk in cases:
        m, k = exp.shape
        table = np.asarray(_skip_table(jnp.asarray(exp, jnp.int8), bm, bk,
                                       n_bits, WEIGHT_BITS))
        grid, in_specs, out_specs, scratch = bitplane_specs(
            m, k, n, WEIGHT_BITS, bm, bn, bk)
        inputs = (
            make_operand("exp", (m, k), jnp.int8, in_specs[0]),
            make_operand("sign", (m, k), jnp.int8, in_specs[1]),
            make_operand("planes", (WEIGHT_BITS, k, n), jnp.uint8,
                         in_specs[2]),
        )
        outputs = (
            make_operand("out", (m, n), jnp.int32, out_specs),
        )
        out.append(KernelInstantiation(
            kernel="bitplane_matmul", case=name, grid=grid,
            inputs=inputs, outputs=outputs,
            scratch=tuple(scratch_entry(s) for s in scratch),
            scalars=(table,),
            meta=dict(exp=np.asarray(exp), n_bits=n_bits, bits=WEIGHT_BITS,
                      block_m=bm, block_k=bk, min_plane=table),
        ))
    return out
