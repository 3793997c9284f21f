"""Plain float32 ``jax.numpy`` reference of the served models.

A pre-norm decoder with grouped-query attention, rotary positions (rotate
half), RMSNorm and a SwiGLU MLP, written from the published architecture
and independent of the program under test.  It runs one layer at a time,
upcasting that layer's weights from their served dtype, so that a 12-layer
qwen2.5-14b stage fits beside its bf16 weights.

Modes (``mode``):

* ``"ref"`` — float32 matmuls at ``Precision.HIGHEST``.
* ``"lower"`` — the control: the same model one precision step below the
  configuration's bfloat16: every projection and the LM head on float8
  (e4m3) operands scaled per channel and per token.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_PAD = 256
ROW_PAD = 128


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def _fp8(x, axis):
    """Round to float8 e4m3 under a power-free absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _proj(x, w, b, spec, mode):
    w = w.astype(jnp.float32)
    if mode == "lower":
        y = _mm(_fp8(x, -1), _fp8(w, 0))
    else:
        y = _mm(x, w)
    return y if b is None else y + b.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def _rope(x, theta):
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, qblock=512):
    """Causal grouped-query attention; q (S, H, D), k/v (S, G, D)."""
    s, h, d = q.shape
    g = k.shape[1]
    qg = q.reshape(s, g, h // g, d)
    nb = max(1, s // qblock) if s % qblock == 0 else 1
    qb = s // nb
    kpos = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, 0)
        sc = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HI) / math.sqrt(d)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, None, None] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(s, h, d)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(spec, mode, x, blk, li):
    p = jax.tree.map(lambda a: a[li], blk)
    s = x.shape[0]
    hd = spec.head_dim
    h = _rms(x, p["ln1"], spec.norm_eps)
    q = _proj(h, p["wq"], p.get("bq"), spec, mode).reshape(s, spec.n_heads, hd)
    k = _proj(h, p["wk"], p.get("bk"), spec, mode).reshape(
        s, spec.n_kv_heads, hd)
    v = _proj(h, p["wv"], p.get("bv"), spec, mode).reshape(
        s, spec.n_kv_heads, hd)
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    o = _attend(q, k, v).reshape(s, spec.n_heads * hd)
    x = x + _proj(o, p["wo"], None, spec, mode)
    h = _rms(x, p["ln2"], spec.norm_eps)
    m = p["mlp"]
    gate = _proj(h, m["gate"], None, spec, mode)
    up = _proj(h, m["up"], None, spec, mode)
    return x + _proj(jax.nn.silu(gate) * up, m["down"], None, spec, mode)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(spec, mode, x, final_norm, head, tokens):
    """Per row: (largest logit, logit of ``tokens``, argmax), over the
    vocabulary in 16 blocks so the f32 head never exists whole."""
    h = _rms(x, final_norm, spec.norm_eps)
    if mode == "lower":
        h = _fp8(h, -1)
    v = head.shape[1]
    nb = 16 if v % 16 == 0 else 1
    vb = v // nb

    def block(carry, i):
        best, arg, tok = carry
        w = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, 1).astype(
            jnp.float32)
        if mode == "lower":
            w = _fp8(w, 0)
        lg = _mm(h, w)
        bm, ba = jnp.max(lg, -1), jnp.argmax(lg, -1) + i * vb
        arg = jnp.where(bm > best, ba, arg)
        best = jnp.maximum(best, bm)
        local = tokens - i * vb
        inb = (local >= 0) & (local < vb)
        got = jnp.take_along_axis(lg, jnp.clip(local, 0, vb - 1)[:, None],
                                  1)[:, 0]
        tok = jnp.where(inb, got, tok)
        return (best, arg, tok), None

    n = x.shape[0]
    init = (jnp.full((n,), -jnp.inf), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32))
    (best, arg, tok), _ = jax.lax.scan(block, init, jnp.arange(nb))
    return best, tok, arg


def _pad(n, m):
    """The power of two at least ``max(n, m)``: few shapes, so few
    compiles."""
    p = m
    while p < n:
        p *= 2
    return p


def hidden(spec, params, tokens, mode="ref"):
    """Final hidden states (before the final norm) of ``tokens``."""
    s = len(tokens)
    ids = np.zeros((_pad(s, SEQ_PAD),), np.int32)
    ids[:s] = tokens
    x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
    blk = params["blocks"][0]
    for li in range(spec.n_layers):
        x = _layer(spec, mode, x, blk, jnp.int32(li))
    return x


def head_of(spec, params):
    return params["embed"].T if spec.tie_embeddings else params["lm_head"]


def _rows(spec, params, prompt, served, mode):
    """Hidden states at the positions that predict each served token (the
    prompt's last position onwards), teacher-forced on ``served``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    x = hidden(spec, params, np.concatenate([prompt, served[:-1]]), mode)
    n = len(served)
    rows = np.zeros((_pad(n, ROW_PAD),), np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return x[jnp.asarray(rows)]


def _score(spec, params, xr, toks, mode):
    n = len(toks)
    t = np.zeros((xr.shape[0],), np.int32)
    t[:n] = toks
    best, tok, arg = _head(spec, mode, xr, params["final_norm"],
                           head_of(spec, params), jnp.asarray(t))
    return np.asarray(best)[:n], np.asarray(tok)[:n], np.asarray(arg)[:n]


def served_gap(spec, params, prompt, served) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position."""
    xr = _rows(spec, params, prompt, served, "ref")
    best, tok, _ = _score(spec, params, xr, served, "ref")
    return float(np.max(best - tok))


def control_gap(spec, params, prompt, served) -> float:
    """The control's reading: at each position of the same prompt and
    served tokens, the reference gap of the token that the lower precision
    puts first."""
    xl = _rows(spec, params, prompt, served, "lower")
    _, _, first = _score(spec, params, xl, served, "lower")
    xr = _rows(spec, params, prompt, served, "ref")
    best, at, _ = _score(spec, params, xr, first, "ref")
    return float(np.max(best - at))
