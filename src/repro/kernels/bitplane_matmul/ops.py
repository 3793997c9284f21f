"""Public jit'd wrapper for the bit-plane matmul Pallas kernel.

Responsibilities: pad (M, K, N) to block multiples, build the scalar-prefetch
``min_plane`` skip table from the activation exponents, invoke the kernel,
unpad.  Also exposes :func:`plane_traffic_fraction`, the HBM-traffic image of
the skip table used by benchmarks (granularity-matched to the kernel tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitplane_matmul.kernel import (WEIGHT_BITS,
                                                  bitplane_matmul_kernel)
from repro.models.sharding import kernel_call, kernel_mesh


def _skip_table(exp: jnp.ndarray, block_m: int, block_k: int,
                n_bits: int, bits: int) -> jnp.ndarray:
    """min_plane[mi, ki] = max(0, -max_exp_tile); 'bits' if tile fully pruned."""
    sentinel = -(1 << (n_bits - 1))
    m, k = exp.shape
    e = exp.astype(jnp.int32).reshape(m // block_m, block_m,
                                      k // block_k, block_k)
    e = jnp.swapaxes(e, 1, 2)                        # (Mb, Kb, bm, bk)
    alive = e != sentinel
    neg_inf = jnp.int32(-128)
    max_e = jnp.max(jnp.where(alive, e, neg_inf), axis=(2, 3))
    min_plane = jnp.clip(-max_e, 0, bits)
    return jnp.where(jnp.any(alive, axis=(2, 3)), min_plane, bits).astype(
        jnp.int32)


# per-dim roles (models.sharding.kernel_call) of (exp, sign, planes) -> out
# for each tensor-parallel weight layout: column-parallel weights hold N on
# the model axis, row-parallel ones K (their partial sums reduce over it)
_TP_LAYOUTS = {
    None: (((None, None), (None, None), (None, None, None)), (None, None)),
    "col": (((None, None), (None, None), (None, None, "m")), (None, "m")),
    "row": (((None, "m"), (None, "m"), (None, "m", None)), (None, None)),
}


def bitplane_matmul_pallas(exp: jnp.ndarray, sign: jnp.ndarray,
                           planes: jnp.ndarray, n_bits: int = 4,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128,
                           interpret: bool | None = None,
                           tp: str | None = None) -> jnp.ndarray:
    """exp/sign int8 (M, K), planes uint8 (8, K, N) -> int32 (M, N).

    ``tp`` names the weight's tensor-parallel layout (``"col"``: N on the
    model axis, ``"row"``: K on it); under a multi-device mesh the kernel
    runs per device on its own weight shard (``kernel_call``)."""
    return _bitplane_matmul(exp, sign, planes, n_bits=n_bits,
                            block_m=block_m, block_n=block_n,
                            block_k=block_k, interpret=interpret,
                            kmesh=kernel_mesh(), tp=tp)


@functools.partial(jax.jit, static_argnames=("n_bits", "block_m", "block_n",
                                             "block_k", "interpret", "kmesh",
                                             "tp"))
def _bitplane_matmul(exp, sign, planes, *, n_bits, block_m, block_n, block_k,
                     interpret, kmesh, tp):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    local = functools.partial(_bitplane_matmul_local, n_bits=n_bits,
                              block_m=block_m, block_n=block_n,
                              block_k=block_k, interpret=interpret)
    in_layouts, out_layout = _TP_LAYOUTS[tp]
    return kernel_call(local, kmesh, in_layouts, out_layout, exp, sign,
                       planes, reduce_model=tp == "row")


def _bitplane_matmul_local(exp, sign, planes, *, n_bits, block_m, block_n,
                           block_k, interpret):
    m, k = exp.shape
    bits, _, n = planes.shape

    pm, pk, pn = (-m) % block_m, (-k) % block_k, (-n) % block_n
    sentinel = -(1 << (n_bits - 1))
    # pad activations with the sentinel (contributes nothing), weights with 0.
    exp_p = jnp.pad(exp, ((0, pm), (0, pk)), constant_values=sentinel)
    sign_p = jnp.pad(sign, ((0, pm), (0, pk)), constant_values=1)
    planes_p = jnp.pad(planes, ((0, 0), (0, pk), (0, pn)))

    table = _skip_table(exp_p, block_m, block_k, n_bits, bits)
    out = bitplane_matmul_kernel(exp_p, sign_p, planes_p, table,
                                 n_bits=n_bits, block_m=block_m,
                                 block_n=block_n, block_k=block_k,
                                 interpret=interpret)
    return out[:m, :n]


def canonical_logquant(shape, sigma: float = 1.0, seed: int = 2,
                       n_bits: int = 4):
    """Deterministic (exp, sign) int8 stream for benches and the static
    kernel audit: N(0, sigma) activations from a fixed numpy generator,
    pushed through the paper's log2 quantizer.  Returned as numpy arrays
    so audit instantiations carry concrete scalar operands."""
    import numpy as np

    from repro.core.logquant import log2_quantize

    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, sigma, shape).astype(np.float32)
    q = log2_quantize(jnp.asarray(x), n_bits=n_bits)
    return np.asarray(q.exp, np.int8), np.asarray(q.sign, np.int8)


def plane_traffic_counts(exp: jnp.ndarray, n_bits: int = 4,
                         block_m: int = 128, block_k: int = 128,
                         bits: int = WEIGHT_BITS):
    """(fetched, total) weight-plane tile counts, as f32 scalars.

    ``total`` is all ``bits`` planes of every (m-tile, k-tile) cell — what a
    standard int8 layout streams; ``fetched`` mirrors the kernel's skip rule
    exactly (same table).  Returned as a pair so callers accumulating over
    many projections (the serving engine's per-step stats) can weight each
    GEMM by its tile count before taking the fraction.
    """
    m, k = exp.shape
    pm, pk = (-m) % block_m, (-k) % block_k
    sentinel = -(1 << (n_bits - 1))
    exp_p = jnp.pad(exp, ((0, pm), (0, pk)), constant_values=sentinel)
    table = _skip_table(exp_p, block_m, block_k, n_bits, bits)
    fetched = jnp.sum(bits - table).astype(jnp.float32)
    total = jnp.asarray(bits * table.size, jnp.float32)
    return fetched, total


def plane_traffic_fraction(exp: jnp.ndarray, n_bits: int = 4,
                           block_m: int = 128, block_k: int = 128,
                           bits: int = WEIGHT_BITS) -> jnp.ndarray:
    """Fraction of weight-plane tiles the kernel actually touches (0..1)."""
    fetched, total = plane_traffic_counts(exp, n_bits, block_m, block_k, bits)
    return fetched / total
