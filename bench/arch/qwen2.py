"""Qwen2: a pre-norm decoder with grouped-query attention (q/k/v biases
optional), rotary positions, RMSNorm and a SwiGLU MLP.

The interface the harness calls is described in ``bench/arch/__init__.py``.
Everything is placed on the cell's first device.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.lib import reference as R


# ------------------------------------------------------------- spec

@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d_model: int
    n_layers: int
    d_ff: int
    vocab_size: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    dtype: str                       # weights and activations as served


def model_spec(doc: dict) -> ModelSpec:
    d = int(doc["hidden_size"])
    heads = int(doc["num_attention_heads"])
    return ModelSpec(
        name=doc["name"],
        d_model=d,
        n_layers=int(doc["num_hidden_layers"]),
        d_ff=int(doc["intermediate_size"]),
        vocab_size=int(doc["vocab_size"]),
        n_heads=heads,
        n_kv_heads=int(doc["num_key_value_heads"]),
        head_dim=int(doc.get("head_dim", d // heads)),
        qkv_bias=bool(doc.get("attention_bias", False)),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        tie_embeddings=bool(doc.get("tie_word_embeddings", False)),
        dtype=doc.get("torch_dtype", "bfloat16"),
    )


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ------------------------------------------------------------- weights

def make_weights(spec, key, devices=None):
    """Seeded random weights, made in one jitted call on ``devices[0]``, in
    the layout the serving program takes (a stacked ``blocks`` period of
    one attention kind) and the dtype the configuration serves."""
    return _jit_build(spec, None if devices is None else devices[0])(key)


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


# ------------------------------------------------------------- program

def program_config(spec):
    """The program's model config for ``spec``."""
    from repro.models.model import ModelConfig

    return ModelConfig(
        name=spec.name, d_model=spec.d_model, n_layers=spec.n_layers,
        d_ff=spec.d_ff, vocab_size=spec.vocab_size, n_heads=spec.n_heads,
        n_kv_heads=spec.n_kv_heads, head_dim=spec.head_dim,
        qkv_bias=spec.qkv_bias, rope_theta=spec.rope_theta,
        norm_eps=spec.norm_eps, tie_embeddings=spec.tie_embeddings,
        dtype=_dtype(spec.dtype))


# ------------------------------------------------------------- reference

@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(spec, mode, x, blk, li):
    p = jax.tree.map(lambda a: a[li], blk)
    s = x.shape[0]
    hd = spec.head_dim
    h = R.rms(x, p["ln1"], spec.norm_eps)
    q = R.proj(h, p["wq"], p.get("bq"), mode).reshape(s, spec.n_heads, hd)
    k = R.proj(h, p["wk"], p.get("bk"), mode).reshape(s, spec.n_kv_heads, hd)
    v = R.proj(h, p["wv"], p.get("bv"), mode).reshape(s, spec.n_kv_heads, hd)
    q, k = R.rope(q, spec.rope_theta), R.rope(k, spec.rope_theta)
    o = R.attend(q, k, v).reshape(s, spec.n_heads * hd)
    x = x + R.proj(o, p["wo"], None, mode)
    h = R.rms(x, p["ln2"], spec.norm_eps)
    m = p["mlp"]
    gate = R.proj(h, m["gate"], None, mode)
    up = R.proj(h, m["up"], None, mode)
    return x + R.proj(jax.nn.silu(gate) * up, m["down"], None, mode)


def hidden(spec, params, tokens, mode="ref"):
    """Final hidden states (before the final norm) of ``tokens``."""
    x = R.embed(params["embed"], tokens)
    blk = params["blocks"][0]
    for li in range(spec.n_layers):
        x = _layer(spec, mode, x, blk, jnp.int32(li))
    return x


def head_of(spec, params):
    return params["embed"].T if spec.tie_embeddings else params["lm_head"]


def _forward(spec, params):
    return R.Forward(functools.partial(hidden, spec, params),
                     params["final_norm"], head_of(spec, params),
                     spec.norm_eps)


def served_gap(spec, params, prompt, served) -> float:
    return R.served_gap(_forward(spec, params), prompt, served)


def control_gap(spec, params, prompt, served) -> float:
    return R.control_gap(_forward(spec, params), prompt, served)


# ------------------------------------------------------------- counts

def layer_matmul_params(spec) -> int:
    """Weights one token multiplies by in one layer (QKV, O, gate, up,
    down)."""
    d, hd = spec.d_model, spec.head_dim
    q, kv = spec.n_heads * hd, spec.n_kv_heads * hd
    return d * q + 2 * d * kv + q * d + 3 * d * spec.d_ff


def head_params(spec) -> int:
    return spec.d_model * spec.vocab_size


def attn_flops(spec, ctx_sum: int) -> float:
    """Score and value products of one token per unit of context, summed:
    4 * layers * heads * head_dim per key."""
    return 4.0 * spec.n_layers * spec.n_heads * spec.head_dim * ctx_sum


def decode_flops(spec, ctx_sum: int, n_tokens: int) -> float:
    per_tok = 2.0 * (spec.n_layers * layer_matmul_params(spec)
                     + head_params(spec))
    return n_tokens * per_tok + attn_flops(spec, ctx_sum)


def prefill_flops(spec, start: int, n: int, head_tokens: int) -> float:
    ctx_sum = n * start + n * (n + 1) // 2
    return (2.0 * n * spec.n_layers * layer_matmul_params(spec)
            + 2.0 * head_tokens * head_params(spec)
            + attn_flops(spec, ctx_sum))


def paged_attn_cost(spec, ctxs, kv_itemsize: int, q_itemsize: int):
    """Bytes are the K and V rows read, plus the queries in and the outputs
    out."""
    hd = spec.head_dim
    ctx_sum = int(sum(ctxs))
    rows = len(ctxs)
    kv = ctx_sum * 2 * spec.n_kv_heads * hd * kv_itemsize
    qo = rows * 2 * spec.n_heads * hd * q_itemsize
    flops = 4.0 * spec.n_heads * hd * ctx_sum
    return flops, float(kv + qo)
