"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the serving program takes (a stacked ``blocks``
period of one attention kind), in the dtype the configuration serves.  The
benchmark owns these arrays: the reference reads the same ones, and takes
nothing that the program derives from them (quantized planes, scales).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def make_weights(spec, key, device=None):
    return _jit_build(spec, device)(key)


def weight_shapes(spec):
    """The tree ``make_weights`` returns, as shapes only."""
    return jax.eval_shape(_jit_build(spec, None), jax.random.PRNGKey(0))


def _jit_build(spec, device):
    """Weights of ``spec`` from ``key``: projections N(0, 1/fan_in),
    embedding and head N(0, 0.02^2), biases N(0, 0.02^2), norm gains
    1 + N(0, 0.1^2)."""
    dt = _dtype(spec.dtype)
    L, d, f, V = spec.n_layers, spec.d_model, spec.d_ff, spec.vocab_size
    q, kv = spec.n_heads * spec.head_dim, spec.n_kv_heads * spec.head_dim

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(dt)

        def gain(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dt)

        blk = {"ln1": gain((L, d)),
               "wq": normal((L, d, q), d ** -0.5),
               "wk": normal((L, d, kv), d ** -0.5),
               "wv": normal((L, d, kv), d ** -0.5),
               "wo": normal((L, q, d), q ** -0.5),
               "ln2": gain((L, d)),
               "mlp": {"gate": normal((L, d, f), d ** -0.5),
                       "up": normal((L, d, f), d ** -0.5),
                       "down": normal((L, f, d), f ** -0.5)}}
        if spec.qkv_bias:
            blk["bq"] = normal((L, q), 0.02)
            blk["bk"] = normal((L, kv), 0.02)
            blk["bv"] = normal((L, kv), 0.02)
        params = {"embed": normal((V, d), 0.02), "blocks": (blk,),
                  "final_norm": gain((d,))}
        if not spec.tie_embeddings:
            params["lm_head"] = normal((d, V), 0.02)
        return params

    shard = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(build, out_shardings=shard)
