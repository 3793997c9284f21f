"""Plain float32 ``jax.numpy`` numerics that every architecture's reference
shares.

An architecture module (``bench/arch/<model_type>.py``) writes its forward
pass from these, from the published architecture and independent of the
program under test, one layer at a time, upcasting that layer's weights
from their served dtype so that a real-width stage fits beside its bf16
weights.  It hands its final hidden states, as a :class:`Forward`, to
:func:`served_gap` and :func:`control_gap`, which score them against the
LM head in blocks.

Modes (``mode``):

* ``"ref"`` — float32 matmuls at ``Precision.HIGHEST``.
* ``"lower"`` — the control: the same model one precision step below the
  configuration's bfloat16: every projection and the LM head on float8
  (e4m3) operands scaled per channel and per token.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_PAD = 256
ROW_PAD = 128


def mm(a, b):
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def fp8(x, axis):
    """Round to float8 e4m3 under a power-free absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def proj(x, w, b, mode):
    """``x @ w (+ b)`` in ``mode``: float32 at HIGHEST, or on float8
    operands scaled per token and per output channel."""
    w = w.astype(jnp.float32)
    if mode == "lower":
        y = mm(fp8(x, -1), fp8(w, 0))
    else:
        y = mm(x, w)
    return y if b is None else y + b.astype(jnp.float32)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def rope(x, theta):
    """Rotary positions 0..S-1 (rotate half); x (S, H, D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(q, k, v, qblock=512):
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


def embed(table, tokens):
    """Float32 embeddings of ``tokens``, the sequence padded with id 0 to a
    power of two of at least ``SEQ_PAD`` (few shapes, so few compiles)."""
    s = len(tokens)
    ids = np.zeros((pad(s, SEQ_PAD),), np.int32)
    ids[:s] = tokens
    return table[jnp.asarray(ids)].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(eps, mode, x, final_norm, head, tokens):
    """Per row: (largest logit, logit of ``tokens``, argmax), over the
    vocabulary in 16 blocks so the f32 head never exists whole."""
    h = rms(x, final_norm, eps)
    if mode == "lower":
        h = fp8(h, -1)
    v = head.shape[1]
    nb = 16 if v % 16 == 0 else 1
    vb = v // nb

    def block(carry, i):
        best, arg, tok = carry
        w = jax.lax.dynamic_slice_in_dim(head, i * vb, vb, 1).astype(
            jnp.float32)
        if mode == "lower":
            w = fp8(w, 0)
        lg = mm(h, w)
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


def pad(n, m):
    """The power of two at least ``max(n, m)``: few shapes, so few
    compiles."""
    p = m
    while p < n:
        p *= 2
    return p


class Forward(NamedTuple):
    """One architecture's reference over one set of weights.
    ``hidden(tokens, mode)`` gives the hidden states of ``tokens`` before
    the final norm (rows past ``len(tokens)`` are padding); the final norm
    gain, the LM head ``(d_model, vocab)`` and the norm's epsilon score
    them."""
    hidden: Callable
    final_norm: Any
    head: Any
    eps: float


def _rows(fwd, prompt, served, mode):
    """Hidden states at the positions that predict each served token (the
    prompt's last position onwards), teacher-forced on ``served``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    x = fwd.hidden(np.concatenate([prompt, served[:-1]]), mode)
    n = len(served)
    rows = np.zeros((pad(n, ROW_PAD),), np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    return x[jnp.asarray(rows)]


def _score(fwd, xr, toks, mode):
    n = len(toks)
    t = np.zeros((xr.shape[0],), np.int32)
    t[:n] = toks
    best, tok, arg = _head(fwd.eps, mode, xr, fwd.final_norm, fwd.head,
                           jnp.asarray(t))
    return np.asarray(best)[:n], np.asarray(tok)[:n], np.asarray(arg)[:n]


def served_gap(fwd: Forward, prompt, served) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position."""
    xr = _rows(fwd, prompt, served, "ref")
    best, tok, _ = _score(fwd, xr, served, "ref")
    return float(np.max(best - tok))


def control_gap(fwd: Forward, prompt, served) -> float:
    """The control's reading: at each position of the same prompt and
    served tokens, the reference gap of the token that the lower precision
    puts first."""
    xl = _rows(fwd, prompt, served, "lower")
    _, _, first = _score(fwd, xl, served, "lower")
    xr = _rows(fwd, prompt, served, "ref")
    best, at, _ = _score(fwd, xr, first, "ref")
    return float(np.max(best - at))
