"""Operations and bytes the served model's work needs, from its shapes.

The counts are the algorithm's, whatever implements it: a padded slab row
or a discarded decode step is not model work.  ``spec`` is a
:class:`bench.lib.spec.ModelSpec`.
"""

from __future__ import annotations


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
    """``n_tokens`` decode steps whose contexts add up to ``ctx_sum``; each
    yields logits."""
    per_tok = 2.0 * (spec.n_layers * layer_matmul_params(spec)
                     + head_params(spec))
    return n_tokens * per_tok + attn_flops(spec, ctx_sum)


def prefill_flops(spec, start: int, n: int, head_tokens: int) -> float:
    """Prompt positions ``start .. start+n-1`` through every layer (causal:
    position p sees p+1 keys); the LM head only for ``head_tokens`` of them
    (the prompt's last token yields the first logits)."""
    ctx_sum = n * start + n * (n + 1) // 2
    return (2.0 * n * spec.n_layers * layer_matmul_params(spec)
            + 2.0 * head_tokens * head_params(spec)
            + attn_flops(spec, ctx_sum))


def paged_attn_cost(spec, ctxs, kv_itemsize: int, q_itemsize: int):
    """One decode-attention call of one layer over rows whose true context
    lengths are ``ctxs``: (flops, bytes).  Bytes are the K and V rows read,
    plus the queries in and the outputs out."""
    hd = spec.head_dim
    ctx_sum = int(sum(ctxs))
    rows = len(ctxs)
    kv = ctx_sum * 2 * spec.n_kv_heads * hd * kv_itemsize
    qo = rows * 2 * spec.n_heads * hd * q_itemsize
    flops = 4.0 * spec.n_heads * hd * ctx_sum
    return flops, float(kv + qo)
