"""A second architecture for the harness tests, added by a new file only:
grouped-query attention with a per-head RMSNorm on queries and keys before
rotary positions (the Qwen3 block), no q/k/v biases, an untied LM head and
a SwiGLU MLP.  ``make_root`` copies it to a test root's
``bench/arch/gqa_qk_norm.py``; the interface is the one
``bench/arch/__init__.py`` describes.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.lib import reference as R


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    d_model: int
    n_layers: int
    d_ff: int
    vocab_size: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    dtype: str


def model_spec(doc):
    d, heads = int(doc["hidden_size"]), int(doc["num_attention_heads"])
    return Spec(name=doc["name"], d_model=d,
                n_layers=int(doc["num_hidden_layers"]),
                d_ff=int(doc["intermediate_size"]),
                vocab_size=int(doc["vocab_size"]), n_heads=heads,
                n_kv_heads=int(doc["num_key_value_heads"]),
                head_dim=int(doc.get("head_dim", d // heads)),
                rope_theta=float(doc["rope_theta"]),
                norm_eps=float(doc["rms_norm_eps"]),
                dtype=doc.get("torch_dtype", "bfloat16"))


def _dtype(name):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def make_weights(spec, key, devices=None):
    return _jit_build(spec, None if devices is None else devices[0])(key)


def weight_shapes(spec):
    return jax.eval_shape(_jit_build(spec, None), jax.random.PRNGKey(0))


def _jit_build(spec, device):
    dt = _dtype(spec.dtype)
    L, d, f, V = spec.n_layers, spec.d_model, spec.d_ff, spec.vocab_size
    hd = spec.head_dim
    q, kv = spec.n_heads * hd, spec.n_kv_heads * hd

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(dt)

        def gain(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dt)

        blk = {"ln1": gain((L, d)), "q_norm": gain((L, hd)),
               "k_norm": gain((L, hd)),
               "wq": normal((L, d, q), d ** -0.5),
               "wk": normal((L, d, kv), d ** -0.5),
               "wv": normal((L, d, kv), d ** -0.5),
               "wo": normal((L, q, d), q ** -0.5),
               "ln2": gain((L, d)),
               "mlp": {"gate": normal((L, d, f), d ** -0.5),
                       "up": normal((L, d, f), d ** -0.5),
                       "down": normal((L, f, d), f ** -0.5)}}
        return {"embed": normal((V, d), 0.02), "blocks": (blk,),
                "final_norm": gain((d,)), "lm_head": normal((d, V), 0.02)}

    shard = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(build, out_shardings=shard)


def program_config(spec):
    from repro.models.model import ModelConfig

    return ModelConfig(
        name=spec.name, d_model=spec.d_model, n_layers=spec.n_layers,
        d_ff=spec.d_ff, vocab_size=spec.vocab_size, n_heads=spec.n_heads,
        n_kv_heads=spec.n_kv_heads, head_dim=spec.head_dim, qk_norm=True,
        rope_theta=spec.rope_theta, norm_eps=spec.norm_eps,
        dtype=_dtype(spec.dtype))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(spec, mode, x, blk, li):
    p = jax.tree.map(lambda a: a[li], blk)
    s, hd, eps = x.shape[0], spec.head_dim, spec.norm_eps
    h = R.rms(x, p["ln1"], eps)
    q = R.proj(h, p["wq"], None, mode).reshape(s, spec.n_heads, hd)
    k = R.proj(h, p["wk"], None, mode).reshape(s, spec.n_kv_heads, hd)
    v = R.proj(h, p["wv"], None, mode).reshape(s, spec.n_kv_heads, hd)
    q = R.rope(R.rms(q, p["q_norm"], eps), spec.rope_theta)
    k = R.rope(R.rms(k, p["k_norm"], eps), spec.rope_theta)
    o = R.attend(q, k, v).reshape(s, spec.n_heads * hd)
    x = x + R.proj(o, p["wo"], None, mode)
    h = R.rms(x, p["ln2"], eps)
    m = p["mlp"]
    gate = R.proj(h, m["gate"], None, mode)
    up = R.proj(h, m["up"], None, mode)
    return x + R.proj(jax.nn.silu(gate) * up, m["down"], None, mode)


def _hidden(spec, params, tokens, mode):
    x = R.embed(params["embed"], tokens)
    for li in range(spec.n_layers):
        x = _layer(spec, mode, x, params["blocks"][0], jnp.int32(li))
    return x


def _forward(spec, params):
    return R.Forward(functools.partial(_hidden, spec, params),
                     params["final_norm"], params["lm_head"], spec.norm_eps)


def served_gap(spec, params, prompt, served):
    return R.served_gap(_forward(spec, params), prompt, served)


def control_gap(spec, params, prompt, served):
    return R.control_gap(_forward(spec, params), prompt, served)


def _layer_params(spec):
    d, hd = spec.d_model, spec.head_dim
    q, kv = spec.n_heads * hd, spec.n_kv_heads * hd
    return 2 * d * q + 2 * d * kv + 3 * d * spec.d_ff


def head_params(spec):
    return spec.d_model * spec.vocab_size


def _attn_flops(spec, ctx_sum):
    return 4.0 * spec.n_layers * spec.n_heads * spec.head_dim * ctx_sum


def decode_flops(spec, ctx_sum, n_tokens):
    return (2.0 * n_tokens * (spec.n_layers * _layer_params(spec)
                              + head_params(spec))
            + _attn_flops(spec, ctx_sum))


def prefill_flops(spec, start, n, head_tokens):
    return (2.0 * n * spec.n_layers * _layer_params(spec)
            + 2.0 * head_tokens * head_params(spec)
            + _attn_flops(spec, n * start + n * (n + 1) // 2))


def paged_attn_cost(spec, ctxs, kv_itemsize, q_itemsize):
    hd, ctx_sum = spec.head_dim, int(sum(ctxs))
    return (4.0 * spec.n_heads * hd * ctx_sum,
            float(ctx_sum * 2 * spec.n_kv_heads * hd * kv_itemsize
                  + len(ctxs) * 2 * spec.n_heads * hd * q_itemsize))
