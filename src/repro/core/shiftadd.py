"""The QeiHaN shift-add dot product — paper Eq. 5 — in three equal forms.

Paper mapping (arXiv 2310.18181; DESIGN.md "Paper ↔ code map"): this module
is the paper's §IV D&S (decode-and-shift) unit datapath — the compute side
of the *implicit bit-shift weight access*: Eq. 5's
``y = sum_i sign_i * ArithShift(w_i, e_i)`` over log2-quantized activations
(``core/logquant.py``, §II/Eqs. 2-4) and bit-plane-stored weights
(``core/bitplane.py``, §IV-B).  The plane-skipping Pallas kernel
(``kernels/bitplane_matmul/``) executes form (2) below with the skipped
fetches made explicit.

Semantics.  An activation quantizes to ``s * 2^e`` (``core.logquant``), a
weight to int8 ``w`` (``core.wquant``).  The D&S unit produces

* ``e >= 0``: ``w << e``  (exact product),
* ``e < 0``:  ``w >> |e|`` arithmetic  ==  ``floor(w / 2^|e|)``  — the LSBs
  of ``w`` shift out of the 16-bit datapath and are **never fetched** from
  memory.  This floor-truncation is the accuracy cost of the paper's memory
  saving, and we model it exactly.

Forms (all return the same int32 tensor, property-tested):

1. :func:`shift_product` / :func:`shiftadd_matmul_elementwise` — direct
   per-element oracle, O(K*N) temporaries; the specification.
2. :func:`shiftadd_matmul_bitplane` — the MXU-friendly regrouping used by
   the Pallas kernel: ``y = sum_b sgn_b * (a_b @ plane_b)`` with
   ``a_b[i] = s_i * 2^(b + e_i) * [b + e_i >= 0]`` (int32) and ``plane_b``
   the ``{0,1}`` bit-plane.  Plane ``b`` contributes nothing for activations
   with ``e_i < -b`` — the *compute* image of the paper's skipped fetches.
3. :func:`shiftadd_matmul_exact` — un-truncated ``sum s_i w_i 2^{e_i}``
   (what the NaHiD/full-fetch datapath computes, and the float reference for
   accuracy ablations).

`QuantizedLinear` wraps the whole path (calibrated activation pre-scale ->
LOG2 quant -> bit-plane matmul -> dequant) as the drop-in projection layer
used by the model zoo when ``QuantConfig.mode == "qeihan"``.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import bitplane as bp
from repro.core.logquant import (LogQuantized, log2_dequantize, log2_quantize,
                                 zero_sentinel)
from repro.core.wquant import QuantizedWeights, quantize_weights

__all__ = [
    "shift_product",
    "shiftadd_matmul_elementwise",
    "shiftadd_matmul_bitplane",
    "shiftadd_matmul_exact",
    "QuantizedLinearParams",
    "QuantCtx",
    "as_quant_ctx",
    "quantized_linear_init",
    "quantized_linear_apply",
    "calibrate_act_scale",
]


def shift_product(w: jnp.ndarray, q: LogQuantized, n_bits: int = 4) -> jnp.ndarray:
    """``sign * Bitshift(w, e)`` with arithmetic right shift; sentinel -> 0."""
    sentinel = zero_sentinel(n_bits)
    w32 = w.astype(jnp.int32)
    e = q.exp.astype(jnp.int32)
    left = w32 << jnp.maximum(e, 0)
    right = w32 >> jnp.maximum(-e, 0)        # arithmetic shift == floor div
    shifted = jnp.where(e >= 0, left, right)
    shifted = jnp.where(e == sentinel, 0, shifted)
    return q.sign.astype(jnp.int32) * shifted


def shiftadd_matmul_elementwise(q: LogQuantized, w: jnp.ndarray,
                                n_bits: int = 4) -> jnp.ndarray:
    """Oracle: ``y[..., n] = sum_k s_k * Bitshift(w[k, n], e_k)``.

    ``q.exp/q.sign``: ``(..., K)``;  ``w``: int8 ``(K, N)``.  O(K*N)
    temporaries — use only for validation / small layers.
    """
    prod = shift_product(w.astype(jnp.int32)[None], LogQuantized(
        exp=q.exp[..., None], sign=q.sign[..., None]), n_bits)
    return jnp.sum(prod, axis=-2)


def shiftadd_matmul_bitplane(q: LogQuantized, planes: jnp.ndarray,
                             n_bits: int = 4,
                             plane_dtype: jnp.dtype = jnp.int32) -> jnp.ndarray:
    """Bit-plane regrouping: 8 {0,1}-matmuls on the MXU.  Exact (int32).

    ``planes``: uint8 ``(bits, K, N)`` from :func:`bitplane.to_bitplanes`.
    Derivation: for ``e < 0``, ``floor(w/2^|e|) = sum_{b >= |e|} c_b 2^e
    plane_b(w)`` with ``c_b = 2^b`` (``-2^7`` for the sign plane), because
    two's-complement floor-shift simply discards low planes.  Folding
    ``c_b * 2^e = sgn_b * 2^(b+e)`` into the activation keeps everything
    integer: ``a_b[k] = s_k * 2^(b+e_k)`` when ``b + e_k >= 0`` else 0.
    """
    bits = planes.shape[0]
    sentinel = zero_sentinel(n_bits)
    e = q.exp.astype(jnp.int32)
    s = q.sign.astype(jnp.int32)
    alive = (e != sentinel)

    out = None
    for b in range(bits):
        sh = b + e
        contrib = alive & (sh >= 0)
        a_b = jnp.where(contrib, s << jnp.maximum(sh, 0), 0)
        term = jnp.matmul(a_b.astype(plane_dtype),
                          planes[b].astype(plane_dtype),
                          preferred_element_type=jnp.int32)
        if b == bits - 1:
            term = -term                      # two's-complement sign plane
        out = term if out is None else out + term
    return out


def shiftadd_matmul_exact(q: LogQuantized, w: jnp.ndarray,
                          n_bits: int = 4) -> jnp.ndarray:
    """Un-truncated ``sum_k s_k w_k 2^{e_k}`` (float32) — NaHiD datapath."""
    a = log2_dequantize(q, n_bits, dtype=jnp.float32)
    return jnp.matmul(a, w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Framework-facing quantized projection layer
# ---------------------------------------------------------------------------

class QuantizedLinearParams(NamedTuple):
    planes: jnp.ndarray       # uint8 (8, K, N) bit-planes of the int8 weight
    w_scale: jnp.ndarray      # f32 per-output-channel scale (1, N)
    act_scale: jnp.ndarray    # f32 scalar pre-scale so acts fit [2^-7, 2^7]
    bias: Optional[jnp.ndarray]


@dataclasses.dataclass(frozen=True, eq=False)
class QuantCtx:
    """Runtime configuration of the QeiHaN quant path, threaded alongside the
    per-projection :class:`QuantizedLinearParams` down to every ``dense``.

    * ``backend`` — ``"xla"`` runs :func:`shiftadd_matmul_bitplane` (8
      unrolled {0,1}-matmuls, the portable form); ``"pallas"`` dispatches the
      plane-skipping TPU kernel (``kernels.bitplane_matmul``), interpret mode
      off-TPU.  Both compute the identical int32 result.
    * ``collect`` — trace-time accumulator: when set (a plain Python list),
      each quantized projection appends ``(tile_fetched, tile_total,
      elem_fetched, elem_total)`` weight-traffic counts — tile-granular
      (what the Pallas kernel's skip table actually DMAs) and
      element-granular (the ASIC bank model, paper Fig. 7) — the traffic
      image of the paper's §VI memory-access savings.  The list must be
      created and consumed within one trace scope (see
      ``models.model.forward``'s scan body).
    """

    backend: str = "xla"
    n_bits: int = 4
    collect: Optional[List[Tuple[jnp.ndarray, jnp.ndarray,
                                 jnp.ndarray, jnp.ndarray]]] = None


def as_quant_ctx(quant: Union[bool, str, QuantCtx, None],
                 default_backend: str = "xla") -> Optional[QuantCtx]:
    """Normalize the user-facing ``quant`` flag: False/None -> None (float
    path), True -> ``QuantCtx(backend=default_backend)``, a backend string or
    an explicit ``QuantCtx`` pass through."""
    if quant is None or quant is False:
        return None
    if isinstance(quant, QuantCtx):
        return quant
    if quant is True:
        return QuantCtx(backend=default_backend)
    if isinstance(quant, str):
        return QuantCtx(backend=quant)
    raise TypeError(f"quant must be bool, str or QuantCtx, got {quant!r}")


def calibrate_act_scale(x: jnp.ndarray, percentile: float = 99.9) -> jnp.ndarray:
    """Per-tensor activation scale: map the p99.9 magnitude to ~2^3.

    LOG2 codes cover [2^-7, 2^7]; centering the distribution's tail at 2^3
    leaves 4 octaves of headroom and 10 octaves below — matching the paper's
    observation that post-norm activations concentrate in (-1, 1).
    """
    mag = jnp.percentile(jnp.abs(x.astype(jnp.float32)), percentile)
    return jnp.maximum(mag, 1e-12) / 8.0


def quantized_linear_init(w: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
                          act_scale: float | jnp.ndarray = 1.0,
                          bits: int = 8) -> QuantizedLinearParams:
    """Offline weight pre-arrangement (paper: 'weights are known statically
    so their organization can be pre-arranged offline')."""
    qw: QuantizedWeights = quantize_weights(w, bits=bits, channel_axis=-1)
    planes = bp.to_bitplanes(qw.q, bits=bits)
    return QuantizedLinearParams(
        planes=planes,
        w_scale=qw.scale.reshape(1, -1),
        act_scale=jnp.asarray(act_scale, jnp.float32),
        bias=bias,
    )


def quantized_linear_apply(p: QuantizedLinearParams, x: jnp.ndarray,
                           n_bits: int = 4,
                           truncated: bool = True,
                           backend: str = "xla",
                           collect: Optional[list] = None,
                           tp: Optional[str] = None) -> jnp.ndarray:
    """x (..., K) -> y (..., N) through the full QeiHaN path.

    ``p.planes`` may be packed 8-to-a-byte along K (the HBM-resident deploy
    format: same footprint as plain INT8); unpacking happens on the fly —
    in-register on the TPU kernel, an explicit op here.

    ``backend="pallas"`` runs the plane-skipping Pallas kernel instead of the
    unrolled jnp bit-plane matmul (identical int32 result); ``collect``
    accumulates ``(fetched, total)`` plane-tile traffic counts (see
    :class:`QuantCtx`).  ``tp`` (``"col"`` / ``"row"``) is the weight's
    tensor-parallel layout, which the kernel needs under a mesh.

    ``x`` and the result pass optimization barriers.  XLA may keep a fused
    producer's or consumer's excess precision (f32 where the dtype says
    bf16), and how much depends on how each backend's program fuses; a
    4-bit log2 code turns that difference into different tokens (on a TPU
    v5e, bf16 smollm-135m served different tokens per backend).  At the
    barriers the values are the ones their dtypes name, whatever the
    backend.
    """
    x = jax.lax.optimization_barrier(x)
    lead = x.shape[:-1]
    k = x.shape[-1]
    planes = p.planes
    if planes.shape[1] * 8 == k:                  # packed along K
        planes = bp.unpack_planes(planes, axis=0)
    xs = (x.astype(jnp.float32) / p.act_scale).reshape(-1, k)
    q = log2_quantize(xs, n_bits=n_bits)
    if collect is not None:
        from repro.core.access_model import needed_bits
        from repro.kernels.bitplane_matmul.ops import plane_traffic_counts
        # weight the per-GEMM fractions by the N extent so the accumulated
        # numbers reflect actual bytes, not tile-table cells
        n_scale = jnp.float32(planes.shape[-1])
        tile_f, tile_t = plane_traffic_counts(q.exp, n_bits=n_bits)
        nb = needed_bits(q.exp, n_bits=n_bits)
        alive = (q.exp != zero_sentinel(n_bits)).astype(jnp.float32)
        collect.append((tile_f * n_scale, tile_t * n_scale,
                        jnp.sum(nb.astype(jnp.float32)) * n_scale,
                        jnp.sum(alive) * 8.0 * n_scale))
    if truncated:
        if backend == "pallas":
            from repro.kernels.bitplane_matmul.ops import bitplane_matmul_pallas
            y_int = bitplane_matmul_pallas(q.exp, q.sign, planes,
                                           n_bits=n_bits, tp=tp)
        else:
            y_int = shiftadd_matmul_bitplane(q, planes, n_bits=n_bits)
        y = y_int.astype(jnp.float32)
    else:
        w = bp.from_bitplanes(planes).astype(jnp.float32)
        y = shiftadd_matmul_exact(q, w, n_bits=n_bits)
    y = y * p.w_scale * p.act_scale
    y = y.reshape(*lead, -1)
    if p.bias is not None:
        y = y + p.bias
    return jax.lax.optimization_barrier(y)
