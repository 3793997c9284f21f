"""FLOP and byte counts of bench/arch/qwen2.py against hand counts, for the
benchmark's configuration."""

import json
from pathlib import Path

import pytest

from bench.arch import qwen2 as F

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _spec(name):
    return F.model_spec(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen_counts_by_hand():
    s = _spec("qwen2.5-14b-l12")
    assert (s.d_model, s.n_layers, s.head_dim, s.n_kv_heads) == (
        5120, 12, 128, 8)
    # q 5120x5120, k and v 5120x1024 each, o 5120x5120, 3 x 5120x13824
    per_layer = 26_214_400 + 2 * 5_242_880 + 26_214_400 + 212_336_640
    assert F.layer_matmul_params(s) == per_layer == 275_251_200
    assert F.head_params(s) == 778_567_680
    # one decode token at context 1000: 2 x (12 layers + head) + attention
    attn = 4 * 12 * 40 * 128 * 1000
    want = 2 * (12 * per_layer + 778_567_680) + attn
    assert F.decode_flops(s, 1000, 1) == want
    # KV bytes of one layer at context 1000 in bf16: 1000 x 2 x 8 x 128 x 2
    fl, by = F.paged_attn_cost(s, [1000], 2, 2)
    assert by == 1000 * 4096 + 2 * 40 * 128 * 2
    assert fl == 4 * 40 * 128 * 1000


def test_prefill_is_causal_sum():
    s = _spec("qwen2.5-14b-l12")
    # three positions from 0: contexts 1 + 2 + 3, one head row
    want = (2 * 3 * 12 * F.layer_matmul_params(s)
            + 2 * F.head_params(s) + 4 * 12 * 40 * 128 * 6)
    assert F.prefill_flops(s, 0, 3, 1) == want
    # a chunk that starts at 10: contexts 11 + 12
    assert F.prefill_flops(s, 10, 2, 0) == pytest.approx(
        2 * 2 * 12 * F.layer_matmul_params(s) + 4 * 12 * 40 * 128 * 23)
