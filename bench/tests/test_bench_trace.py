"""The trace reduction on a small recorded trace: the busy union, programs
and kernels found by pattern, idle time inside the tick spans, and the
readers that turn them into per-layer metrics."""

import json
import types

import pytest

from bench.lib import trace as T
from bench.lib.drive import TickRec
from bench.arch import qwen2


def small_trace():
    """Two ticks on one device: a decode tick (program 1.0-1.6 s holding two
    kernel calls and a fusion that overlaps one of them) and a mixed tick
    (2.0-2.5 s), inside host spans 0.9-1.7 and 1.9-2.6, with host
    bookkeeping between them."""
    mods = [T.Ev("jit_tick_paged(7)", 1.0, 1.6),
            T.Ev("jit_mixed_paged(8)", 2.0, 2.5)]
    ops = [T.Ev("_paged_decode_attention.1", 1.0, 1.1),
           T.Ev("fusion.3", 1.05, 1.2),
           T.Ev("_paged_decode_attention.1", 1.3, 1.4),
           T.Ev("fusion.9", 2.0, 2.4),
           T.Ev("_paged_decode_attention.2", 2.4, 2.45)]
    spans = [T.Ev("bench.host", 0.8, 0.9), T.Ev("bench.tick", 0.9, 1.7),
             T.Ev("bench.host", 1.7, 1.9), T.Ev("bench.tick", 1.9, 2.6)]
    tr = T.Trace(ops, mods, spans)
    T._tag_modules(tr.ops, tr.modules)
    return tr


def test_recorded_trace_round_trips(tmp_path):
    tr = small_trace()
    T.save_json(tr, tmp_path / "t.json")
    back = T.load_json(tmp_path / "t.json")
    assert [e.__dict__ for e in back.ops] == [e.__dict__ for e in tr.ops]
    assert json.loads((tmp_path / "t.json").read_text())["n_devices"] == 1


def test_busy_union_and_idle_in_spans():
    tr = small_trace()
    # 1.0-1.2 (two ops overlap) + 1.3-1.4 + 2.0-2.45
    assert T.busy(tr, 0.8, 2.6) == pytest.approx(0.2 + 0.1 + 0.45)
    assert T.busy(tr, 1.15, 1.35) == pytest.approx(0.05 + 0.05)
    idle_first_tick = 0.8 - T.busy(tr, 0.9, 1.7)
    assert idle_first_tick == pytest.approx(0.5)


def test_attribution_by_pattern():
    tr = small_trace()
    assert [e.module for e in tr.ops] == ["jit_tick_paged(7)"] * 3 + [
        "jit_mixed_paged(8)"] * 2
    kern = T.select(tr.ops, r"^_paged_decode_attention", r"^jit_(tick|mixed)_paged")
    assert T.total(kern) == pytest.approx(0.25)
    assert T.total(T.select(tr.ops, r"^_paged_decode_attention", r"^jit_tick")) == \
        pytest.approx(0.2)
    assert T.total(T.select(tr.ops, "", "", 1.9, 2.6)) == pytest.approx(0.45)
    top = dict(T.top_ops(tr, 0.8, 2.6))
    assert top["jit_mixed_paged/fusion"] == pytest.approx(0.4)
    assert top["jit_tick_paged/_paged_decode_attention"] == pytest.approx(0.2)


def test_idle_gaps_named_by_host_span():
    gaps = T.idle_gaps(small_trace(), 0.8, 2.6)
    # 1.4-2.0 is the longest, its middle in host bookkeeping; 1.2-1.3
    # idles inside the first tick's span
    assert gaps[0][0] == "bench.host" and gaps[0][1] == pytest.approx(0.6)
    assert any(n == "bench.tick" and g == pytest.approx(0.1)
               for n, g in gaps)
    assert T.op_name("%fusion.6 = bf16[4]{0} fusion(bf16[4] %x)") == \
        "fusion.6"


def _ctx(tr):
    spec = qwen2.ModelSpec(name="t", d_model=8, n_layers=2, d_ff=16,
                           vocab_size=32, n_heads=2, n_kv_heads=1,
                           head_dim=4, qkv_bias=False, rope_theta=1e4,
                           norm_eps=1e-5, tie_embeddings=True,
                           dtype="bfloat16")
    t1 = TickRec(0.9, 1.7, 2, decode=[(10, 0, 2), (20, 5, 2)], chunk=[],
                 prefill=[], traced=True)
    t2 = TickRec(1.9, 2.6, 2, decode=[(10, 2, 2)], chunk=[(0, 8)],
                 prefill=[], traced=True)
    t1.a, t1.b, t2.a, t2.b = 0.9, 1.7, 1.9, 2.6
    serve = types.SimpleNamespace(tick_steps=2, max_slots=4)
    return types.SimpleNamespace(trace=tr, ticks=[t1, t2],
                                 trace_window=(0.8, 2.6), arch=qwen2,
                                 spec=spec, serve=serve,
                                 device_kind="TPU v5 lite",
                                 window=types.SimpleNamespace(
                                     ticks=[t1, t2], end=3.0))


def test_readers_on_the_recorded_trace():
    from bench.run import reader
    from bench.run import ROOT

    ctx = _ctx(small_trace())
    idle = reader(ROOT, "device_idle_pct.tps")(ctx)
    assert idle == pytest.approx(100 * (1 - 0.75 / 1.8))
    gap = reader(ROOT, "host_gap_ms_per_tick.tps")(ctx)
    assert gap == pytest.approx(1e3 * ((0.8 - 0.3) + (0.7 - 0.45)) / 2)
    occ = reader(ROOT, "slot_occupancy_pct.tps")(ctx)
    assert occ == pytest.approx(50.0)
    roof = reader(ROOT, "paged_attn_roofline.tps")(ctx)
    assert 0 < roof < 100
    mfu = reader(ROOT, "tick_mfu_pct.tps")(ctx)
    assert 0 < mfu < 100


def test_recorded_chip_trace_excerpt():
    """16 ms of a mixed tick of qwen14b-l12.decode-batch, recorded on a TPU
    v5e: 143 device ops, two of them the paged-attention kernel."""
    from pathlib import Path

    tr = T.load_json(Path(__file__).parent / "data" /
                     "mixed_tick_excerpt.json")
    (span,) = tr.spans
    # the busy union by brute force: 10 us steps over the span
    import numpy as np
    grid = np.arange(span.start, span.end, 1e-5) + 5e-6
    on = np.zeros(grid.shape, bool)
    for e in tr.ops:
        on |= (grid >= e.start) & (grid < e.end)
    busy = T.busy(tr, span.start, span.end)
    assert busy == pytest.approx(on.mean() * (span.end - span.start),
                                 abs=2e-5)
    assert busy < span.end - span.start       # the span starts idle
    kern = T.select(tr.ops, r"^_paged_decode_attention",
                    r"^jit_(tick|mixed)_paged")
    assert len(kern) == 2
    assert all(e.module.startswith("jit_mixed_paged") for e in tr.ops)
    assert T.total(kern) == pytest.approx(0.010375475, rel=1e-6)
