"""Shared layer primitives for the model zoo (pure-functional, pytree params)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.shiftadd import (QuantCtx, QuantizedLinearParams, as_quant_ctx,
                                 quantized_linear_apply, quantized_linear_init)

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, k: int, n: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(k))
    return (jax.random.normal(key, (k, n), jnp.float32) * scale).astype(dtype)


def embed_init(key, v: int, d: int, dtype):
    return (jax.random.normal(key, (v, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * w


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * w + b


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 1e4) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense projection (float or QeiHaN-quantized)
# ---------------------------------------------------------------------------

def dense(w, x: jnp.ndarray, bias=None,
          quant: Optional[QuantizedLinearParams] = None,
          ctx=None, tp: Optional[str] = None) -> jnp.ndarray:
    """Projection with optional QeiHaN path.

    ``w``: (K, N); ``x``: (..., K).  When ``quant`` is provided the GEMM runs
    through the LOG2-activation / bit-plane-weight shift-add path (the
    framework's first-class integration of the paper's technique).  ``ctx``
    (bool | str | QuantCtx) selects the backend ("xla" | "pallas") and
    optionally collects plane-traffic counts; see ``core.shiftadd.QuantCtx``.
    ``tp`` is the weight's tensor-parallel layout (``"col"``: output dim on
    the model axis, ``"row"``: input dim), as ``launch.shardings`` lays it
    out; the bit-plane kernel needs it under a mesh.
    """
    if quant is not None:
        qc = as_quant_ctx(ctx) or QuantCtx()
        y = quantized_linear_apply(quant, x, n_bits=qc.n_bits,
                                   backend=qc.backend,
                                   collect=qc.collect, tp=tp).astype(x.dtype)
    else:
        y = jnp.matmul(x, w.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def quantize_dense(w, bias=None, act_scale: float = 1.0) -> QuantizedLinearParams:
    return quantized_linear_init(jnp.asarray(w, jnp.float32), bias=bias,
                                 act_scale=act_scale)


def swiglu(p, x: jnp.ndarray, quant=False) -> jnp.ndarray:
    """p: {'gate': (d, ff), 'up': (d, ff), 'down': (ff, d)}.

    ``quant`` is the usual bool | str | QuantCtx flag (truthy enables the
    QeiHaN path and is forwarded to ``dense`` as the backend/stats context).
    """
    g = dense(p["gate"], x, quant=p.get("gate_q") if quant else None,
              ctx=quant, tp="col")
    u = dense(p["up"], x, quant=p.get("up_q") if quant else None, ctx=quant,
              tp="col")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    from repro.models.sharding import shard
    h = shard(h, "btf")
    return dense(p["down"], h, quant=p.get("down_q") if quant else None,
                 ctx=quant, tp="row")
