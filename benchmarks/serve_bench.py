"""Continuous-batching serve benchmark: sustained tok/s, per-request
latency (TTFT + end-to-end p50/p95), per-tick latency, and plane traffic
under a Poisson request trace.

Compares the slot-pool scheduler (``serving/scheduler.py`` — admit /
tick / retire / re-fill, decode never drains) against the *naive serial*
baseline: each request decoded alone through the fused ``greedy_generate``
program, one after another — what you get without a scheduler.  Both sides
are timed warm (compile excluded); baseline prompts are padded to the same
buckets so its compile count is bounded identically.  A second scheduler
pass runs the quantized bit-plane path with per-request
``plane_traffic_fraction`` / ``element_traffic_fraction`` reporting — the
sustained-load image of the paper's §VI memory-access savings.

The **chunked** variant (``serve_bench_chunked`` / ``--chunked``) is the
ISSUE 4 A/B: the same heavy mixed trace (short interactive prompts +
prompts at the largest bucket) replayed through monolithic bucketed
prefill vs chunked prefill (``chunked="always"``), reporting the p95
scheduler-tick latency both ways — the head-of-line stall a monolithic
prefill inflicts on in-flight decodes, removed — plus a long-prompt trace
(prompts past the largest bucket) that only the chunked scheduler can
serve at all.

The **kv-quant** variant (``serve_bench_kv_quant`` / ``--kv-quant``) is the
ISSUE 9 A/B: one deterministic trace through the paged scheduler with dense
f32 pages vs log2-quantized pages (``kv_quant=True``), both on the fused
Pallas kernel — tok/s and TTFT head to head, the per-request token
divergence rate, and the EXACT-gated static pool-byte model (>= 2x fewer
pool bytes per request at 4-bit, tail ring included).

The **sharded** variant (``serve_bench_sharded`` / ``--sharded``) replays
the same trace through a mesh-native scheduler (``mesh='2x2'`` data x model
by default) in a SUBPROCESS with forced host devices — the parent process
keeps its single real device — and asserts token parity against the
single-device scheduler before reporting throughput; it also runs a
chunked-``"auto"`` parity pass with over-bucket prompts.

  PYTHONPATH=src python -m benchmarks.serve_bench            # full bench
  PYTHONPATH=src python -m benchmarks.serve_bench --chunked  # ISSUE 4 A/B
  PYTHONPATH=src python -m benchmarks.serve_bench --dry      # CI smoke
  PYTHONPATH=src python -m benchmarks.serve_bench --sharded  # mesh variant
  PYTHONPATH=src python -m benchmarks.run --only serve       # via driver

Rows print as ``serve.<name>,<value>,`` CSV like every other bench; each
bench pass additionally emits ONE machine-readable ``# json {...}`` line
(ignored by the CSV consumers) carrying the summary metrics and the
per-request records (rid, prompt_len, ttft_s, e2e_s, finish_reason) — the
artifact downstream dashboards ingest, smoke-validated in ``--dry`` CI.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from typing import List, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_trace(rng, n_requests: int, vocab: int, min_len: int, max_len: int,
                rate: float) -> List[Tuple[float, np.ndarray]]:
    """Poisson arrivals (exponential gaps at ``rate`` req/s; ``rate=0`` =
    everything queued at t=0) with uniform prompt lengths."""
    arrivals, t = [], 0.0
    for _ in range(n_requests):
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        prompt = rng.integers(0, vocab,
                              size=int(rng.integers(min_len, max_len + 1)),
                              ).astype(np.int32)
        arrivals.append((t, prompt))
    return arrivals


def _run_scheduler(sched, trace, max_new: int, eos_id=None):
    """Replay the trace in wall-clock time (fast-forwarding idle gaps);
    returns (results-so-far in rid order, elapsed_busy_seconds,
    per-step_tick wall seconds).  Every tick syncs tokens to host, so the
    clock reads true device-done time; a tick's duration includes the
    admissions it performed — monolithic prefill stalls show up HERE."""
    pending = list(trace)
    t0 = time.perf_counter()
    idle = 0.0
    tick_times: List[float] = []
    while pending or sched.pending:
        now = time.perf_counter() - t0 - idle
        while pending and pending[0][0] <= now:
            _, prompt = pending.pop(0)
            sched.submit(prompt, max_new=max_new, eos_id=eos_id)
        if sched.pending:
            tt = time.perf_counter()
            sched.step_tick()
            tick_times.append(time.perf_counter() - tt)
        elif pending:
            # fast-forward an empty system to the next arrival: idle time is
            # not "sustained load" and is excluded from the throughput
            idle += pending[0][0] - now
    return (sched.run(max_ticks=0), time.perf_counter() - t0 - idle,
            tick_times)


def _pct(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else float("nan")


def _request_records(results):
    """Per-request latency records from the scheduler's result timestamps
    (one time.perf_counter clock): ttft = queue wait + prefill up to the
    first generated token; e2e = submit -> retirement."""
    recs = []
    for r in results:
        recs.append({
            "rid": r.rid, "prompt_len": r.prompt_len,
            "finish_reason": r.finish_reason,
            "ttft_s": (r.first_token_time - r.submit_time
                       if np.isfinite(r.first_token_time) else float("nan")),
            "e2e_s": (r.finish_time - r.submit_time
                      if np.isfinite(r.finish_time) else float("nan")),
        })
    return recs


def _latency_rows(prefix: str, results, tick_times):
    """TTFT / end-to-end p50+p95 (ms) over SERVED requests (a rejected
    request's ~0 s turnaround is finite and would deflate the e2e
    percentiles) + p50/p95 per-scheduler-tick latency (ms) — the satellite
    metrics next to tok/s."""
    recs = _request_records(results)
    served = [x for x in recs if x["finish_reason"] != "rejected"]
    ttft = [x["ttft_s"] for x in served if np.isfinite(x["ttft_s"])]
    e2e = [x["e2e_s"] for x in served if np.isfinite(x["e2e_s"])]
    nan = float("nan")
    return [
        (f"{prefix}.ttft_p50_ms", _pct(ttft, 50) * 1e3, nan),
        (f"{prefix}.ttft_p95_ms", _pct(ttft, 95) * 1e3, nan),
        (f"{prefix}.e2e_p50_ms", _pct(e2e, 50) * 1e3, nan),
        (f"{prefix}.e2e_p95_ms", _pct(e2e, 95) * 1e3, nan),
        (f"{prefix}.tick_p50_ms", _pct(tick_times, 50) * 1e3, nan),
        (f"{prefix}.tick_p95_ms", _pct(tick_times, 95) * 1e3, nan),
    ], recs


def _emit_json(bench: str, rows, recs=None) -> None:
    """One machine-readable summary line per bench pass (CSV consumers skip
    ``#`` lines).  json.dumps doubles as the serializability check that
    ``--dry`` CI exercises."""
    obj = {"bench": bench,
           "rows": {name: (None if isinstance(val, float) and np.isnan(val)
                           else float(val)) for name, val, _ in rows}}
    if recs is not None:
        obj["per_request"] = [
            {k: (None if isinstance(v, float) and np.isnan(v) else v)
             for k, v in r.items()} for r in recs]
    print("# json " + json.dumps(obj))


def _warm_trace(rng, buckets, vocab) -> List[Tuple[float, np.ndarray]]:
    """One request per bucket at t=0 — compiles every prefill variant plus
    the tick program before anything is timed."""
    return [(0.0, rng.integers(0, vocab, size=b).astype(np.int32))
            for b in buckets]


def serve_bench(arch: str = "smollm_135m", n_requests: int = 24,
                max_slots: int = 8, tick_steps: int = 8, max_new: int = 24,
                rate: float = 0.0, seed: int = 0,
                buckets: Tuple[int, ...] = (8, 16, 32)):
    """Returns rows (name, value, reference-nan) for benchmarks.run."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.models.quantize import quantize_model_params
    from repro.serving import engine
    from repro.serving.config import ServeConfig
    from repro.serving.scheduler import ServeScheduler, bucket_for

    cfg = get_smoke(arch).replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    pool_len = max(buckets) + max_new + tick_steps
    trace = _make_trace(rng, n_requests, cfg.vocab_size,
                        min_len=4, max_len=max(buckets), rate=rate)
    total_tokens = n_requests * max_new
    nan = float("nan")
    rows = []

    # --- naive serial baseline: fused generate, one request at a time ------
    key = jax.random.PRNGKey(0)

    def serial_pass():
        for _, prompt in trace:
            b = bucket_for(prompt.size, buckets)
            padded = np.zeros((1, b), np.int32)
            padded[0, :prompt.size] = prompt
            fn = engine.generate_fn(cfg, max_new, 0.0, False, None, False)
            jax.block_until_ready(fn(params, jnp.asarray(padded), key)[0])

    serial_pass()                                    # warm every bucket
    t0 = time.perf_counter()
    serial_pass()
    t_serial = time.perf_counter() - t0
    rows.append((f"serve.{cfg.name}.serial_tok_s",
                 total_tokens / t_serial, nan))

    # --- continuous-batching scheduler, float ------------------------------
    sched = ServeScheduler(cfg, params,
                           ServeConfig(max_slots=max_slots, max_len=pool_len,
                                       buckets=buckets,
                                       tick_steps=tick_steps))
    _run_scheduler(sched, _warm_trace(rng, buckets, cfg.vocab_size), max_new)
    results, t_sched, ticks = _run_scheduler(sched, trace, max_new)
    got = sum(len(r.tokens) for r in results[-n_requests:])
    assert got == total_tokens, (got, total_tokens)
    rows.append((f"serve.{cfg.name}.sched_tok_s",
                 total_tokens / t_sched, nan))
    rows.append((f"serve.{cfg.name}.sched_vs_serial_speedup",
                 t_serial / t_sched, nan))
    lat_rows, recs = _latency_rows(f"serve.{cfg.name}.sched",
                                   results[-n_requests:], ticks)
    rows += lat_rows

    # --- quantized pass with per-request traffic stats ---------------------
    qparams = quantize_model_params(cfg, params)
    qsched = ServeScheduler(cfg, qparams,
                            ServeConfig(max_slots=max_slots,
                                        max_len=pool_len, buckets=buckets,
                                        quant="xla", with_stats=True,
                                        tick_steps=tick_steps))
    _run_scheduler(qsched, _warm_trace(rng, buckets, cfg.vocab_size),
                   max_new)
    qresults, t_q, _ = _run_scheduler(qsched, trace, max_new)
    qresults = qresults[-n_requests:]
    rows.append((f"serve.{cfg.name}.quant.sched_tok_s",
                 total_tokens / t_q, nan))
    rows.append((f"serve.{cfg.name}.quant.plane_traffic_fraction_tile",
                 float(np.mean([r.plane_traffic_fraction
                                for r in qresults])), nan))
    rows.append((f"serve.{cfg.name}.quant.plane_traffic_fraction_element",
                 float(np.mean([r.element_traffic_fraction
                                for r in qresults])), nan))
    _emit_json("serve", rows, recs)
    return rows


def serve_bench_chunked(arch: str = "smollm_135m", n_requests: int = 24,
                        max_slots: int = 8, tick_steps: int = 8,
                        max_new: int = 16, seed: int = 0,
                        buckets: Tuple[int, ...] = (8, 16, 32)):
    """ISSUE 4 A/B: heavy mixed traffic (half short interactive prompts,
    half at the largest bucket) through monolithic bucketed prefill vs
    chunked prefill, p95 scheduler-tick latency head to head — then a
    long-prompt trace (up to 3x the largest bucket) that monolithic prefill
    would reject outright, served chunked, with TTFT/e2e percentiles."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.serving.config import ServeConfig
    from repro.serving.scheduler import ServeScheduler, round_pool_len

    cfg = get_smoke(arch).replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    chunk_len = buckets[0]
    long_max = 3 * max(buckets)
    pool_len = round_pool_len(long_max + max_new + tick_steps, chunk_len)
    nan = float("nan")
    rows = []

    # --- A/B: same in-bucket heavy-mix trace, monolithic vs chunked --------
    # (in-bucket so BOTH sides can serve it; half the prompts sit at the
    # largest bucket — each monolithic admission stalls every decode slot
    # for a full bucket prefill, the chunked side ingests chunk_len/tick)
    mix = []
    for i in range(n_requests):
        n = (max(buckets) if i % 2 == 0
             else int(rng.integers(4, buckets[0] + 1)))
        mix.append((0.0, rng.integers(0, cfg.vocab_size,
                                      size=n).astype(np.int32)))
    warm = _warm_trace(rng, buckets, cfg.vocab_size)
    p95 = {}
    for label, kw in (("mono", {}), ("chunked", {"chunked": "always"})):
        sched = ServeScheduler(cfg, params,
                               ServeConfig(max_slots=max_slots,
                                           max_len=pool_len, buckets=buckets,
                                           tick_steps=tick_steps, **kw))
        _run_scheduler(sched, warm, max_new)
        results, t, ticks = _run_scheduler(sched, mix, max_new)
        results = results[-n_requests:]
        total = sum(len(r.tokens) for r in results)
        rows.append((f"serve.{cfg.name}.chunk_ab[{label}].tok_s",
                     total / t, nan))
        lat, _ = _latency_rows(f"serve.{cfg.name}.chunk_ab[{label}]",
                               results, ticks)
        rows += lat
        p95[label] = _pct(ticks, 95)
    rows.append((f"serve.{cfg.name}.chunk_ab.p95_tick_speedup",
                 p95["mono"] / p95["chunked"], nan))

    # --- long prompts: beyond every bucket, serveable only chunked ---------
    longs = [(0.0, rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(max(buckets) + 1,
                                                      long_max + 1)),
                                ).astype(np.int32))
             for _ in range(max(2, n_requests // 3))]
    sched = ServeScheduler(cfg, params,
                           ServeConfig(max_slots=max_slots, max_len=pool_len,
                                       buckets=buckets,
                                       tick_steps=tick_steps,
                                       chunked="auto"))
    _run_scheduler(sched, warm + longs[:1], max_new)
    results, t, ticks = _run_scheduler(sched, longs, max_new)
    results = results[-len(longs):]
    served = [r for r in results if r.finish_reason == "length"]
    assert len(served) == len(longs), \
        [r.finish_reason for r in results]      # no rejections: the point
    rows.append((f"serve.{cfg.name}.long.served_frac",
                 len(served) / len(longs), nan))
    rows.append((f"serve.{cfg.name}.long.tok_s",
                 sum(len(r.tokens) for r in served) / t, nan))
    lat, recs = _latency_rows(f"serve.{cfg.name}.long", results, ticks)
    rows += lat
    _emit_json("serve_chunked", rows, recs)
    return rows


def serve_bench_prefix(arch: str = "smollm_135m", n_requests: int = 24,
                       max_slots: int = 4, tick_steps: int = 8,
                       max_new: int = 16, seed: int = 0,
                       prefix_len: int = 48, page_len: int = 16,
                       buckets: Tuple[int, ...] = (16, 64)):
    """ISSUE 5 ``--prefix-trace``: a shared-system-prompt workload — every
    request is one long common prefix plus a short unique tail — replayed
    through the dense ServeScheduler and the paged+prefix-cache scheduler.

    Reports the prefix hit rate, the fraction of prefill cache-write
    traffic the radix cache eliminated (each cached token skips its
    per-layer K/V writes AND its prefill compute — the serving-level image
    of the paper's §VI avoided memory accesses), and TTFT p50/p95 head to
    head.  The first ``max_slots`` admissions necessarily miss (the donor
    retires before its pages become shareable); every later admission hits.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.serving.config import ServeConfig
    from repro.serving.scheduler import ServeScheduler, round_pool_len

    cfg = get_smoke(arch).replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, size=prefix_len).astype(np.int32)
    trace = []
    for _ in range(n_requests):
        tail = rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(4, 13))).astype(np.int32)
        trace.append((0.0, np.concatenate([prefix, tail])))
    pool_len = round_pool_len(prefix_len + 16 + max_new + tick_steps,
                              page_len)
    nan = float("nan")
    rows = []
    ttft95 = {}
    for label, kw in (("dense", {}),
                      ("paged", dict(paged=True, page_len=page_len,
                                     prefix_cache=True, chunked="auto",
                                     chunk_len=page_len))):
        sched = ServeScheduler(cfg, params,
                               ServeConfig(max_slots=max_slots,
                                           max_len=pool_len, buckets=buckets,
                                           tick_steps=tick_steps, **kw))
        _run_scheduler(sched, _warm_trace(rng, buckets, cfg.vocab_size),
                       max_new)
        if label == "paged":
            # warm the HIT-path programs too (suffix chunk ingestion, the
            # mixed chunk+decode tick, prefix-hit admission, partial-block
            # COW): a throwaway shared-prefix family — its prefix differs
            # from the timed trace's, so the timed hit accounting is clean.
            # Sequential waves: the donor must RETIRE before a lookup can
            # hit its pages.
            wp = rng.integers(0, cfg.vocab_size,
                              size=2 * page_len + 3).astype(np.int32)
            tails = [rng.integers(0, cfg.vocab_size,
                                  size=4).astype(np.int32) for _ in range(3)]
            _run_scheduler(sched,
                           [(0.0, np.concatenate([wp, tails[0]]))], max_new)
            _run_scheduler(sched,
                           [(0.0, np.concatenate([wp, tails[1]]))], max_new)
            _run_scheduler(sched,
                           [(0.0, np.concatenate([wp, tails[2]])),
                            (0.0, rng.integers(0, cfg.vocab_size,
                                               size=8).astype(np.int32))],
                           max_new)
            sched.reset_prefix_stats()
        results, t, ticks = _run_scheduler(sched, trace, max_new)
        results = results[-n_requests:]
        total = sum(len(r.tokens) for r in results)
        assert total == n_requests * max_new, (total, n_requests * max_new)
        rows.append((f"serve.{cfg.name}.prefix[{label}].tok_s",
                     total / t, nan))
        lat, recs = _latency_rows(f"serve.{cfg.name}.prefix[{label}]",
                                  results, ticks)
        rows += lat
        ttft95[label] = next(v for n, v, _ in lat if "ttft_p95" in n)
        if label == "paged":
            st = sched.prefix_cache_stats()
            rows.append((f"serve.{cfg.name}.prefix.hit_rate",
                         st["hit_rate"], nan))
            rows.append((f"serve.{cfg.name}.prefix.cache_write_saved_frac",
                         st["cache_write_saved_frac"], nan))
            rows.append((f"serve.{cfg.name}.prefix.lookup_hits",
                         st["lookup_hits"], nan))
    rows.append((f"serve.{cfg.name}.prefix.ttft_p95_speedup",
                 ttft95["dense"] / ttft95["paged"], nan))
    _emit_json("serve_paged", rows, recs)
    return rows


def serve_bench_kv_quant(arch: str = "smollm_135m", n_requests: int = 16,
                         max_slots: int = 4, tick_steps: int = 4,
                         max_new: int = 16, seed: int = 0,
                         page_len: int = 4, kv_bits: int = 4,
                         min_prompt: int = 32,
                         buckets: Tuple[int, ...] = (16, 32, 48)):
    """ISSUE 9 ``--kv-quant``: the same deterministic trace through the
    paged scheduler dense vs log2-quantized (``kv_quant=True``), both on
    the fused Pallas paged-attention kernel.

    Reports tok/s + TTFT/e2e percentiles head to head (advisory), the
    per-request token divergence (``token_bit_equal_frac`` — EXACT-gated:
    given the committed seed the quantized stream is deterministic, so any
    drift is a behavior change), and the static byte model (EXACT-gated,
    pure arithmetic from ``kvpool.page_kv_bytes`` / ``tail_ring_bytes``,
    not measurement): pool bytes per request with the quant side charged
    its full f32 tail-ring working set, the pool-write traffic a completed
    page costs (codes + scale vs f32 rows — the §VI cache-write image),
    and the pool-bytes reduction, asserted >= 2x at 4-bit."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.serving.config import ServeConfig
    from repro.serving.kvpool import (blocks_for_tokens, page_kv_bytes,
                                      tail_ring_bytes)
    from repro.serving.scheduler import ServeScheduler, round_pool_len

    cfg = get_smoke(arch).replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    trace = _make_trace(rng, n_requests, cfg.vocab_size,
                        min_len=min_prompt, max_len=max(buckets), rate=0.0)
    pool_len = round_pool_len(max(buckets) + max_new + tick_steps, page_len)
    nan = float("nan")
    rows = []
    tokens = {}
    tok_s = {}
    for label, kw in (("dense", {}),
                      ("quant", dict(kv_quant=True, kv_bits=kv_bits))):
        sched = ServeScheduler(cfg, params,
                               ServeConfig(max_slots=max_slots,
                                           max_len=pool_len, buckets=buckets,
                                           tick_steps=tick_steps, paged=True,
                                           page_len=page_len,
                                           attn_kernel=True,
                                           attn_splits=2, **kw))
        _run_scheduler(sched, _warm_trace(rng, buckets, cfg.vocab_size),
                       max_new)
        results, t, ticks = _run_scheduler(sched, trace, max_new)
        results = results[-n_requests:]
        total = sum(len(r.tokens) for r in results)
        assert total == n_requests * max_new, (total, n_requests * max_new)
        tokens[label] = [r.tokens for r in results]
        tok_s[label] = total / t
        rows.append((f"serve.{cfg.name}.kvq[{label}].tok_s",
                     total / t, nan))
        lat, recs = _latency_rows(f"serve.{cfg.name}.kvq[{label}]",
                                  results, ticks)
        rows += lat
    rows.append((f"serve.{cfg.name}.kvq.quant_vs_dense_tok_s_ratio",
                 tok_s["quant"] / tok_s["dense"], nan))
    equal = [int(a == b) for a, b in zip(tokens["dense"], tokens["quant"])]
    rows.append((f"serve.{cfg.name}.kvq.token_bit_equal_frac",
                 sum(equal) / n_requests, nan))

    # --- static byte model (pure arithmetic; both sides hold the same page
    # count, so page_len cancels out of the saved fractions) ---------------
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    n_attn = cfg.repeats * sum(1 for k in cfg.pattern
                               if k.split("_")[0] != "mamba")
    pages = sum(blocks_for_tokens(p.size + max_new, page_len)
                for _, p in trace)
    dense_pool = pages * page_kv_bytes(page_len, kv, hd, layers=n_attn)
    quant_pool = (pages * page_kv_bytes(page_len, kv, hd, layers=n_attn,
                                        quant=True, kv_bits=kv_bits)
                  + max_slots * tail_ring_bytes(page_len, kv, hd,
                                                layers=n_attn))
    # pool-WRITE traffic: dense writes every token row in f32; quant writes
    # each completed page once as codes + one scale per (page, head).  The
    # per-token tail-ring writes land in the small per-slot ring, not the
    # pool — its full footprint is already charged to quant_pool above.
    dense_write = dense_pool
    quant_write = pages * page_kv_bytes(page_len, kv, hd, layers=n_attn,
                                        quant=True, kv_bits=kv_bits)
    rows.append((f"serve.{cfg.name}.kvq[dense].pool_bytes_per_request",
                 dense_pool / n_requests, nan))
    rows.append((f"serve.{cfg.name}.kvq[quant].pool_bytes_per_request",
                 quant_pool / n_requests, nan))
    rows.append((f"serve.{cfg.name}.kvq.pool_bytes_saved_frac",
                 1.0 - quant_pool / dense_pool, nan))
    rows.append((f"serve.{cfg.name}.kvq.pool_write_saved_frac",
                 1.0 - quant_write / dense_write, nan))
    reduction = dense_pool / quant_pool
    # the ISSUE 9 acceptance bar: >= 2x fewer pool bytes per request on the
    # int8 wire format (2..7 exponent bits); 8-bit codes widen to int16 and
    # land near 1.7x, a documented trade, not a regression
    if kv_bits < 8:
        assert reduction >= 2.0, (reduction, dense_pool, quant_pool)
    rows.append((f"serve.{cfg.name}.kvq.pool_bytes_reduction_x",
                 reduction, nan))
    rows.append((f"serve.{cfg.name}.kvq.tail_ring_bytes_per_slot",
                 float(tail_ring_bytes(page_len, kv, hd, layers=n_attn)),
                 nan))
    _emit_json("kv_quant", rows, recs)
    return rows


def _sharded_child(arch: str, n_requests: int, max_slots: int,
                   tick_steps: int, max_new: int, seed: int,
                   buckets: Tuple[int, ...], mesh_spec: str):
    """Runs INSIDE the forced-multi-device subprocess: single-device vs
    mesh-sharded scheduler over the same trace — parity asserted, both
    throughputs reported."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.launch.mesh import make_serve_mesh
    from repro.models import init_params

    cfg = get_smoke(arch).replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    pool_len = max(buckets) + max_new + tick_steps
    trace = _make_trace(rng, n_requests, cfg.vocab_size,
                        min_len=4, max_len=max(buckets), rate=0.0)
    rows = []
    tokens = {}
    chunk_tokens = {}
    # over-bucket prompts for the chunked parity leg (monolithic rejects
    # them; the chunked scheduler must serve them identically on a mesh)
    chunk_trace = trace[: max(2, n_requests // 4)] + [
        (0.0, rng.integers(0, cfg.vocab_size,
                           size=2 * max(buckets)).astype(np.int32))]
    from repro.serving.scheduler import round_pool_len
    chunk_pool = round_pool_len(2 * max(buckets) + max_new + tick_steps,
                                buckets[0])
    for label, mesh in (("single", None),
                        (mesh_spec, make_serve_mesh(mesh_spec))):
        from repro.serving.config import ServeConfig
        from repro.serving.scheduler import ServeScheduler
        sched = ServeScheduler(cfg, params,
                               ServeConfig(max_slots=max_slots,
                                           max_len=pool_len, buckets=buckets,
                                           tick_steps=tick_steps),
                               mesh=mesh)
        _run_scheduler(sched, _warm_trace(rng, buckets, cfg.vocab_size),
                       max_new)
        results, t, _ = _run_scheduler(sched, trace, max_new)
        tokens[label] = [r.tokens for r in results[-n_requests:]]
        rows.append((f"serve.{cfg.name}.sharded[{label}].tok_s",
                     n_requests * max_new / t, float("nan")))
        csched = ServeScheduler(cfg, params,
                                ServeConfig(max_slots=max_slots,
                                            max_len=chunk_pool,
                                            buckets=buckets,
                                            tick_steps=tick_steps,
                                            chunked="auto"),
                                mesh=mesh)
        cresults, _, _ = _run_scheduler(csched, chunk_trace, max_new)
        assert all(r.finish_reason == "length" for r in cresults), cresults
        chunk_tokens[label] = [r.tokens for r in cresults]
    assert tokens["single"] == tokens[mesh_spec], \
        "sharded scheduler tokens diverged from single-device"
    rows.append((f"serve.{cfg.name}.sharded[{mesh_spec}].bit_equal",
                 1.0, float("nan")))
    assert chunk_tokens["single"] == chunk_tokens[mesh_spec], \
        "sharded CHUNKED scheduler tokens diverged from single-device"
    rows.append((f"serve.{cfg.name}.sharded[{mesh_spec}].chunked_bit_equal",
                 1.0, float("nan")))
    return rows


def serve_bench_sharded(arch: str = "smollm_135m", n_requests: int = 16,
                        max_slots: int = 8, tick_steps: int = 8,
                        max_new: int = 16, seed: int = 0,
                        buckets: Tuple[int, ...] = (8, 16, 32),
                        mesh_spec: str = "2x2", devices: int = 4):
    """Mesh-sharded serve bench: spawns a CPU subprocess with ``devices``
    forced host devices (the calling process' jax stays single-device) and
    parses its CSV rows.  Registered in ``benchmarks.run`` as
    ``serve_sharded``.  Refused when this process holds a TPU: the child
    could not open the chip, and a CPU child's timings are no chip
    measurement."""
    from repro.launch.runtime import cpu_child_env, refuse_if_chip_held

    refuse_if_chip_held("serve_bench_sharded")
    args = ["--child-sharded", "--arch", arch,
            "--requests", str(n_requests), "--max-slots", str(max_slots),
            "--tick-steps", str(tick_steps), "--new-tokens", str(max_new),
            "--seed", str(seed), "--mesh", mesh_spec,
            "--buckets", ",".join(str(b) for b in buckets)]
    env = cpu_child_env(
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.pathsep.join(
            [_REPO, os.path.join(_REPO, "src"),
             os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.serve_bench"] + args,
        capture_output=True, text=True, timeout=1200, env=env, cwd=_REPO)
    if out.returncode != 0:
        raise RuntimeError(f"sharded serve bench child failed:\n"
                           f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
    rows = []
    for line in out.stdout.splitlines():
        if line.startswith("serve."):
            name, val, _ = line.split(",")
            rows.append((name, float(val), float("nan")))
    if not rows:
        raise RuntimeError(f"sharded serve bench child produced no rows:\n"
                           f"{out.stdout}")
    # the bit_equal / chunked_bit_equal rows are correctness metrics the
    # bench-drift gate (tools/bench_check.py) checks exactly
    _emit_json("serve_sharded", rows)
    return rows


def serve_bench_disagg(arch: str = "smollm_135m", n_short: int = 12,
                       n_long: int = 4, max_slots: int = 4,
                       tick_steps: int = 4, max_new: int = 16,
                       seed: int = 0, page_len: int = 8,
                       buckets: Tuple[int, ...] = (8, 16)):
    """ISSUE 10 A/B: decode saturation + prefill flood, combined scheduler
    vs the disaggregated prefill/decode router (``serving/router.py``) on
    the SAME paged config and trace.

    The trace is ``n_short`` short interactive prompts (they keep the
    decode slots saturated) interleaved with ``n_long`` long prompts at 3x
    the largest bucket (each floods prefill with chunked ingestion).  In
    the combined scheduler every long prompt's chunk rides the same jitted
    mixed tick as the in-flight decodes — the per-tick latency the decode
    traffic observes inflates.  The router runs the same ingestion on the
    PREFILL engine; the decode fleet's ticks are pure decode by
    construction.  Reported: token parity (EXACT-gated — the disaggregated
    stream must be bit-equal to the combined scheduler), tok/s both ways
    (advisory), p95 tick latency both ways, and the isolation ratio
    (combined p95 tick / decode-fleet p95 tick, advisory) — the acceptance
    claim that a prefill flood does not regress decode tick latency."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.serving.config import ServeConfig
    from repro.serving.router import Router
    from repro.serving.scheduler import ServeScheduler, round_pool_len

    cfg = get_smoke(arch).replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    chunk_len = buckets[0]
    long_max = 3 * max(buckets)
    quantum = math.lcm(chunk_len, page_len)
    config = ServeConfig(max_slots=max_slots,
                         max_len=round_pool_len(
                             long_max + max_new + tick_steps, quantum),
                         buckets=buckets, tick_steps=tick_steps,
                         chunked="auto", chunk_len=chunk_len,
                         paged=True, page_len=page_len)
    # interleave: every (short, short, long) group keeps decodes live
    # while a long prompt floods prefill
    trace, li = [], 0
    for i in range(n_short + n_long):
        if n_long and i % ((n_short + n_long) // n_long) == 2 and li < n_long:
            n = long_max
            li += 1
        else:
            n = int(rng.integers(4, max(buckets) + 1))
        trace.append((0.0, rng.integers(0, cfg.vocab_size,
                                        size=n).astype(np.int32)))
    warm = _warm_trace(rng, buckets, cfg.vocab_size) + [
        (0.0, rng.integers(0, cfg.vocab_size,
                           size=long_max).astype(np.int32))]
    nan = float("nan")
    rows = []

    # --- combined: chunk ingestion and decode share every tick ------------
    sched = ServeScheduler(cfg, params, config)
    _run_scheduler(sched, warm, max_new)
    results, t_comb, comb_ticks = _run_scheduler(sched, trace, max_new)
    results = results[-len(trace):]
    total = sum(len(r.tokens) for r in results)
    rows.append((f"serve.{cfg.name}.disagg[combined].tok_s",
                 total / t_comb, nan))
    rows.append((f"serve.{cfg.name}.disagg[combined].tick_p95_ms",
                 _pct(comb_ticks, 95) * 1e3, nan))

    # --- disaggregated: same config through the router --------------------
    router = Router(cfg, params, config)
    for _, prompt in warm:
        router.submit(prompt, max_new=max_new)
    router.run()
    router.decode_tick_times.clear()
    for _, prompt in trace:
        router.submit(prompt, max_new=max_new)
    t0 = time.perf_counter()
    dresults = router.run()
    t_dis = time.perf_counter() - t0
    dtotal = sum(len(r.tokens) for r in dresults)
    rows.append((f"serve.{cfg.name}.disagg[router].tok_s",
                 dtotal / t_dis, nan))
    rows.append((f"serve.{cfg.name}.disagg[decode].tick_p95_ms",
                 _pct(router.decode_tick_times, 95) * 1e3, nan))

    # token parity: the disaggregated stream must be bit-equal (EXACT gate)
    equal = (len(results) == len(dresults) and all(
        a.tokens == b.tokens and a.finish_reason == b.finish_reason
        for a, b in zip(results, dresults)))
    rows.append((f"serve.{cfg.name}.disagg.tokens_bit_equal",
                 float(equal), nan))
    assert equal, "disaggregated tokens diverged from combined scheduler"
    # the TTFT-isolation claim: decode-fleet ticks don't pay for prefill
    rows.append((f"serve.{cfg.name}.disagg.isolation_p95_speedup",
                 _pct(comb_ticks, 95) / _pct(router.decode_tick_times, 95),
                 nan))
    lat, recs = _latency_rows(f"serve.{cfg.name}.disagg[router]",
                              dresults, router.decode_tick_times)
    rows += lat
    _emit_json("serve_disagg", rows, recs)
    return rows


ALL_SERVE_BENCHES = {"serve": serve_bench,
                     "serve_chunked": serve_bench_chunked,
                     "serve_paged": serve_bench_prefix,
                     "serve_kv_quant": serve_bench_kv_quant,
                     "serve_sharded": serve_bench_sharded,
                     "serve_disagg": serve_bench_disagg}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--tick-steps", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = all queued "
                         "at t=0, the sustained-load trace)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry", action="store_true",
                    help="CI smoke: tiny trace, checks wiring + that the "
                         "scheduler runs end-to-end (single-device, chunked "
                         "A/B + long prompts, AND a 2x2 sharded pass incl. "
                         "chunked parity), and validates the # json rows")
    ap.add_argument("--chunked", action="store_true",
                    help="run the chunked-prefill A/B (monolithic vs "
                         "chunked p95 tick latency + long-prompt trace)")
    ap.add_argument("--prefix-trace", action="store_true",
                    help="run the shared-system-prompt workload through the "
                         "dense vs paged+prefix-cache schedulers (hit rate, "
                         "cache-write traffic saved, TTFT p50/p95 A/B)")
    ap.add_argument("--prefix-len", type=int, default=48,
                    help="shared prefix length for --prefix-trace")
    ap.add_argument("--page-len", type=int, default=16,
                    help="KV page size for --prefix-trace")
    ap.add_argument("--kv-quant", action="store_true",
                    help="run the log2-quantized KV-page A/B (dense-paged "
                         "vs kv_quant scheduler: tok/s, TTFT, token "
                         "divergence, EXACT-gated pool-byte savings)")
    ap.add_argument("--kv-bits", type=int, default=4,
                    help="wire exponent bits for --kv-quant")
    ap.add_argument("--disaggregated", action="store_true",
                    help="run the disaggregated A/B (combined scheduler vs "
                         "prefill/decode router: token parity EXACT, decode "
                         "tick-latency isolation under prefill flood)")
    ap.add_argument("--sharded", action="store_true",
                    help="run the mesh-sharded variant (subprocess with "
                         "forced host devices)")
    ap.add_argument("--mesh", default="2x2",
                    help="DxM mesh spec for the sharded variant")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count for the sharded variant")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated prefill buckets (child mode)")
    ap.add_argument("--child-sharded", action="store_true",
                    help=argparse.SUPPRESS)   # internal: runs inside the
                                              # forced-device subprocess
    args = ap.parse_args(argv)

    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()

    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else (8, 16, 32))
    if args.child_sharded:
        rows = _sharded_child(args.arch, args.requests, args.max_slots,
                              args.tick_steps, args.new_tokens, args.seed,
                              buckets, args.mesh)
    elif args.dry and args.disaggregated:
        # the multidevice-CI smoke: ONLY the tiny disaggregated A/B (the
        # full --dry suite runs it too, alongside everything else)
        rows = serve_bench_disagg(args.arch, n_short=4, n_long=2,
                                  max_slots=2, tick_steps=2, max_new=4,
                                  seed=args.seed, page_len=8,
                                  buckets=(8, 16))
        names = [n for n, _, _ in rows]
        for want in ("disagg.tokens_bit_equal",
                     "disagg.isolation_p95_speedup"):
            assert any(want in n for n in names), (want, names)
    elif args.dry:
        rows = serve_bench(args.arch, n_requests=4, max_slots=2,
                           tick_steps=2, max_new=4, rate=args.rate,
                           seed=args.seed, buckets=(8, 16))
        rows += serve_bench_chunked(args.arch, n_requests=4, max_slots=2,
                                    tick_steps=2, max_new=4, seed=args.seed,
                                    buckets=(8, 16))
        rows += serve_bench_prefix(args.arch, n_requests=6, max_slots=2,
                                   tick_steps=2, max_new=4, seed=args.seed,
                                   prefix_len=16, page_len=8,
                                   buckets=(8, 32))
        rows += serve_bench_kv_quant(args.arch, n_requests=6, max_slots=2,
                                     tick_steps=2, max_new=4, seed=args.seed,
                                     page_len=4, kv_bits=4, min_prompt=12,
                                     buckets=(8, 16))
        rows += serve_bench_sharded(args.arch, n_requests=4, max_slots=2,
                                    tick_steps=2, max_new=4, seed=args.seed,
                                    buckets=(8, 16), mesh_spec=args.mesh,
                                    devices=args.devices)
        rows += serve_bench_disagg(args.arch, n_short=4, n_long=2,
                                   max_slots=2, tick_steps=2, max_new=4,
                                   seed=args.seed, page_len=8,
                                   buckets=(8, 16))
        # the --dry contract: the latency satellites exist in the emitted
        # rows (CI drift check for the TTFT/p95 reporting)
        names = [n for n, _, _ in rows]
        for want in ("ttft_p50_ms", "ttft_p95_ms", "e2e_p50_ms",
                     "e2e_p95_ms", "tick_p95_ms", "p95_tick_speedup",
                     "long.served_frac", "chunked_bit_equal",
                     "prefix.hit_rate", "prefix.cache_write_saved_frac",
                     "kvq.token_bit_equal_frac", "kvq.pool_bytes_saved_frac",
                     "kvq.pool_bytes_reduction_x",
                     "disagg.tokens_bit_equal",
                     "disagg.isolation_p95_speedup"):
            assert any(want in n for n in names), (want, names)
        # prefix-cache smoke: the shared-prefix trace must actually HIT
        hits = [v for n, v, _ in rows if n.endswith("prefix.lookup_hits")]
        assert hits and hits[0] > 0, rows
    elif args.chunked:
        rows = serve_bench_chunked(args.arch, n_requests=args.requests,
                                   max_slots=args.max_slots,
                                   tick_steps=args.tick_steps,
                                   max_new=args.new_tokens, seed=args.seed,
                                   buckets=buckets)
    elif args.prefix_trace:
        rows = serve_bench_prefix(args.arch, n_requests=args.requests,
                                  max_slots=args.max_slots,
                                  tick_steps=args.tick_steps,
                                  max_new=args.new_tokens, seed=args.seed,
                                  prefix_len=args.prefix_len,
                                  page_len=args.page_len)
    elif args.kv_quant:
        rows = serve_bench_kv_quant(args.arch, n_requests=args.requests,
                                    max_slots=args.max_slots,
                                    tick_steps=args.tick_steps,
                                    max_new=args.new_tokens, seed=args.seed,
                                    kv_bits=args.kv_bits)
    elif args.disaggregated:
        rows = serve_bench_disagg(args.arch,
                                  n_short=max(2, args.requests * 3 // 4),
                                  n_long=max(1, args.requests // 4),
                                  max_slots=args.max_slots,
                                  tick_steps=args.tick_steps,
                                  max_new=args.new_tokens, seed=args.seed,
                                  page_len=args.page_len)
    elif args.sharded:
        rows = serve_bench_sharded(args.arch, n_requests=args.requests,
                                   max_slots=args.max_slots,
                                   tick_steps=args.tick_steps,
                                   max_new=args.new_tokens, seed=args.seed,
                                   buckets=buckets,
                                   mesh_spec=args.mesh, devices=args.devices)
    else:
        rows = serve_bench(args.arch, n_requests=args.requests,
                           max_slots=args.max_slots,
                           tick_steps=args.tick_steps,
                           max_new=args.new_tokens, rate=args.rate,
                           seed=args.seed)
    print("name,value,paper_reference")
    for name, val, _ in rows:
        print(f"{name},{val:.4f},")


if __name__ == "__main__":
    main()
