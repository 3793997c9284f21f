"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in the file that the manifest names, its architecture in
``bench/arch/<model_type>.py`` (``model_type`` as the configuration file
gives it; the interface is in ``bench/arch/__init__.py``), its traffic mix
in ``bench/traffic/<mix>.json`` and each metric's reader in
``bench/metrics/<stem>.py`` (``<stem>`` is the metric's name before its
first ``.``).  Set-up builds the program's serving scheduler from the
configuration, makes the weights on the device from the seed, warms up
the shapes the mix reaches and runs the mix's priming ticks; the window
then drives the scheduler (``bench.lib.drive``); afterwards the served
tokens of a seeded sample of finished requests are checked against the
plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device`` and, traced, ``breakdown``;
the numbers compared against their limits come last, under ``compared``.
A run that finds no TPU, or fewer chips than the cell asks for, prints no
result and exits with code 3.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from bench.lib import loadgen  # noqa: E402
from bench.lib.drive import drive  # noqa: E402


def _process_age() -> float:
    """Seconds since this process started (Linux, 10 ms resolution), or
    since this module was imported where ``/proc`` cannot say."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_START


# ------------------------------------------------------------- manifest

def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(manifest: dict, workload: str):
    wl = next((w for w in manifest["workloads"] if w["name"] == workload),
              None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in manifest["configs"] if c["name"] == wl["config"])
    return wl, conf


def _in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def metrics_for(manifest: dict, workload: str, trace: bool):
    e2e = [m for m in manifest["end_to_end"] if _in(m, workload)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def _load(name: str, path: Path):
    found = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(found)
    sys.modules[name] = mod          # a dataclass looks its module up there
    found.loader.exec_module(mod)
    return mod


def reader(root: Path, name: str):
    stem = name.split(".")[0]
    return _load(f"bench_metric_{stem}",
                 root / "bench" / "metrics" / f"{stem}.py").read


def load_doc(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_arch(root: Path, doc: dict):
    """The architecture module that the configuration's ``model_type``
    names: ``<root>/bench/arch/<model_type>.py``."""
    where = root / "bench" / "arch"
    mt = doc.get("model_type")
    if mt is None:
        raise SystemExit(f"configuration {doc.get('name')!r} has no "
                         f"\"model_type\" key, which names its architecture "
                         f"module {where}/<model_type>.py")
    path = where / f"{mt}.py"
    if not re.fullmatch(r"\w+", str(mt)) or not path.is_file():
        raise SystemExit(f"configuration {doc.get('name')!r}: \"model_type\" "
                         f"{mt!r} names no architecture module: {path} "
                         f"not found")
    return _load(f"bench_arch_{mt}", path)


# ------------------------------------------------------------- program

def build(arch, doc: dict, spec, weights):
    """The program under test: its model config and its serving scheduler
    over ``weights``."""
    from repro.serving import ServeConfig, ServeScheduler

    cfg = arch.program_config(spec)
    serve = ServeConfig(**doc["serve"])
    return cfg, serve, ServeScheduler(cfg, weights, serve)


def probes(sched):
    def probe():
        out = {}
        for s in sched._slots:
            if s is not None:
                plen = int(s.req.prompt.size)
                pos = s.prefill_pos if s.phase == "prefill" else plen
                out[s.req.rid] = (plen, pos, len(s.tokens))
        return out

    def retired():
        return {rid: (len(r.tokens), r.finish_reason == "rejected")
                for rid, r in sched._results.items()}
    return probe, retired


def warm_lengths(serve, traffic):
    """Prompt lengths that reach every program the mix's prompts reach:
    the shortest in each bucket they use, and the shortest past the
    largest bucket where the mix chunks (as (short, long))."""
    from repro.serving.scheduler import bucket_for

    top = serve.buckets[-1]
    chunk_above = {"auto": top, "always": 0}.get(serve.chunked, 1 << 30)
    short, seen = [], set()
    for n in traffic.prompt_lengths():
        if n > chunk_above:
            return short, [n]
        b = bucket_for(n, serve.buckets)
        if b not in seen:
            seen.add(b)
            short.append(n)
    return short, []


def warm_up(sched, serve, traffic) -> None:
    """Compile every program the mix reaches through the scheduler itself:
    a chunked prompt alone (chunk program), then one prompt per reachable
    bucket beside it (prefill per bucket, slot write, mixed and decode
    ticks)."""
    short, long = warm_lengths(serve, traffic)
    rng = loadgen._rng(traffic.seed, 9)
    new = 2 * serve.tick_steps
    for n in long:
        sched.submit(rng.integers(0, traffic.vocab, n, dtype="int32"), new)
        # twice: a program's first call on the freshly built pool is a
        # variant of its own, which the window never meets
        sched.step_tick()
        sched.step_tick()
    for n in short:
        sched.submit(rng.integers(0, traffic.vocab, n, dtype="int32"), new)
    sched.run()


class CompileCounter:
    """Counts JAX compilation events (compiles and persistent-cache reads)
    while armed."""

    def __init__(self):
        import jax
        self.n = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and ("compile" in event or "cache_retrieval" in event):
            self.n += 1


# ------------------------------------------------------------- the run

def _seed32(seed: int) -> int:
    return int(loadgen._rng(seed, 0).integers(0, 2 ** 31 - 1))


def sample(win, seed: int, want: int):
    """Requests to check, among those that finished after the window
    opened: the one with the most served tokens, then ``want - 1`` others
    in a seeded order."""
    done = [r for r in win.every_req if r.retired and not r.rejected
            and r.n_tok and r.retired_at >= win.start]
    if not done:
        return []
    done.sort(key=lambda r: (-r.n_tok, r.index))
    first, rest = done[0], done[1:]
    order = loadgen._rng(seed, 3).permutation(len(rest))
    return [first] + [rest[i] for i in order[:max(0, want - 1)]]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
    return info


def setup_cell(workload: str, seed: int, root: Path = ROOT, fault=None):
    """Everything before the window: the configuration, the weights made
    on the chip from the seed, the scheduler, the warm-up.  ``fault``, for
    tests, is called with the scheduler after set-up and may break the
    timed path underneath."""
    import jax

    from repro.launch.runtime import enable_compile_cache

    manifest = load_manifest(root)
    wl, conf = cell(manifest, workload)
    doc = load_doc(root / conf["file"])
    arch = load_arch(root, doc)
    mix = json.loads((root / "bench" / "traffic"
                      / f"{wl['traffic']}.json").read_text())
    spec = arch.model_spec(doc)
    chips = int(wl["chips"])
    devices = jax.devices()[:chips]

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # float32 as the configuration states it: a TPU otherwise takes float32
    # matmuls in one bf16 pass
    jax.config.update("jax_default_matmul_precision",
                      "highest" if spec.dtype == "float32" else None)
    counter = CompileCounter()

    weights = arch.make_weights(spec, jax.random.PRNGKey(_seed32(seed)),
                                devices)
    traffic = loadgen.Traffic(mix, seed, spec.vocab_size)
    cfg, serve, sched = build(arch, doc, spec, weights)
    warm_up(sched, serve, traffic)
    if fault is not None:
        fault(sched)
    return types.SimpleNamespace(
        root=root, manifest=manifest, workload=workload, seed=seed, doc=doc,
        mix=mix, arch=arch, spec=spec, chips=chips, devices=devices,
        counter=counter, weights=weights, traffic=traffic, serve=serve,
        sched=sched)


def window(c, seconds: float, trace: bool, keep_trace=None):
    """The mix's priming ticks, then one measured window on a set-up cell;
    returns the metric context (the window's records, and with ``trace``
    the reduced trace).  ``setup_s`` ends where the window opens."""
    import jax

    sched, serve, mix = c.sched, c.serve, c.traffic.mix
    probe, retired = probes(sched)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    kw = {}
    if trace:
        ts = min(float(mix.get("trace_s", seconds)), seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0         # host spans only, no Python
        kw = dict(span=lambda name: jax.profiler.TraceAnnotation(name),
                  trace_at=(max(0.0, (seconds - ts) / 2), ts),
                  start_trace=lambda: jax.profiler.start_trace(
                      trace_dir, profiler_options=opts),
                  stop_trace=jax.profiler.stop_trace)
    opened = {}

    def arm():
        # the window opens: set-up ends, and compiles count from here
        opened["setup_s"] = _process_age()
        opened["compiles"] = sched.compile_stats()
        c.counter.n, c.counter.armed = 0, True

    win = drive(sched, c.traffic, seconds=seconds,
                drain_s=float(mix.get("drain_s", 60)), probe=probe,
                retired=retired, prime_ticks=int(mix.get("prime_ticks", 0)),
                on_open=arm,
                chunk_above={"auto": serve.buckets[-1], "always": 0}.get(
                    serve.chunked, 1 << 30), **kw)
    c.counter.armed = False
    before, after = opened["compiles"], sched.compile_stats()
    ctx = types.SimpleNamespace(
        window=win, arch=c.arch, spec=c.spec, serve=serve, chips=c.chips,
        setup_s=opened["setup_s"], seconds=win.seconds,
        device_kind=c.devices[0].device_kind, trace=None, ticks=[],
        trace_window=None, window_loop=mix["loop"], mix=mix,
        compiles_before=before, compiles_after=after,
        compiles_in_window=c.counter.n + sum(after[k] - before[k]
                                             for k in after),
        device=device_info(c.devices), breakdown=None)
    if trace:
        from bench.lib import trace as tr_lib
        tr = tr_lib.load(trace_dir)
        if keep_trace:
            tr_lib.save_json(tr, keep_trace)
            Path(keep_trace + ".planes.json").write_text(
                json.dumps(tr_lib.describe(trace_dir), indent=1))
        shutil.rmtree(trace_dir, ignore_errors=True)
        traced = [t for t in win.every_tick if t.traced]
        ticks = [s for s in tr.spans if s.name == "bench.tick"]
        if len(ticks) != len(traced):
            raise RuntimeError(f"{len(ticks)} tick spans in the trace for "
                               f"{len(traced)} traced ticks")
        for t, s in zip(traced, ticks):
            t.a, t.b = s.start, s.end
        a, b = tr.spans[0].start, tr.spans[-1].end
        ctx.trace, ctx.ticks, ctx.trace_window = tr, traced, (a, b)
        ctx.device["busy_s"] = tr_lib.busy(tr, a, b)
        ctx.device["window_s"] = b - a
        ctx.breakdown = {
            "device_ops": [list(x) for x in tr_lib.top_ops(tr, a, b)],
            "idle_gaps": [list(x) for x in tr_lib.idle_gaps(tr, a, b)]}
    return ctx


def check(c, win, control: bool = False):
    """Frees the scheduler, then reads the served tokens of a seeded sample
    of finished requests against the reference: the widest gap by which a
    served token's reference logit lies below the reference's best.  With
    ``control``, also the control's reading at the same positions (the
    reference one precision step below the configuration, put in the
    program's place).  Returns ({"program": gap[, "control": gap]},
    requests checked, tokens checked, seconds)."""
    chosen = sample(win, c.seed, int(c.mix.get("check_requests", 8)))
    served = {r.rid: list(c.sched._results[r.rid].tokens) for r in chosen}
    c.sched = None
    gc.collect()
    t0 = time.perf_counter()
    gaps = {"program": -math.inf}
    if control:
        gaps["control"] = -math.inf
    for r in chosen:
        prompt = c.traffic.request(r.index).prompt
        gaps["program"] = max(gaps["program"], c.arch.served_gap(
            c.spec, c.weights, prompt, served[r.rid]))
        if control:
            gaps["control"] = max(gaps["control"], c.arch.control_gap(
                c.spec, c.weights, prompt, served[r.rid]))
    return (gaps, len(chosen), sum(len(v) for v in served.values()),
            time.perf_counter() - t0)


def judge(gap: float, n_req: int, limit: float):
    """``correct`` and the numbers compared: the widest logit gap of the
    checked tokens against the configuration's limit."""
    return (n_req > 0 and gap <= limit,
            {"logit_gap": {"value": gap, "limit": limit}})


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, out_dir=None, keep_trace=None,
             fault=None, control: bool = False) -> dict:
    """One run of one cell; returns the result object (the last line).
    ``control`` reads the control beside the program and judges it the
    same way, under the key ``control`` (the benchmark's own runs do not
    read it)."""
    c = setup_cell(workload, seed, root, fault)
    ctx = window(c, seconds, trace, keep_trace)
    win = ctx.window
    metrics = {}
    for m in metrics_for(c.manifest, workload, trace):
        v = reader(root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    gaps, n_req, n_tok, ref_s = check(c, win, control)
    limit = float(c.doc["check"]["max_logit_gap"])
    correct, compared = judge(gaps["program"], n_req, limit)
    failed = sum(1 for r in win.reqs
                 if r.rejected or math.isnan(r.first)
                 or (c.mix["loop"] == "open" and not r.retired))
    late = sorted(win.lateness) or [0.0]
    kinds = {}
    for t in win.ticks:
        kinds[t.kind] = kinds.get(t.kind, 0) + 1
    report = {
        "workload": workload, "seed": seed, "trace": int(trace),
        "setup_s": ctx.setup_s, "prime_ticks": int(c.mix.get(
            "prime_ticks", 0)),
        "window_s": win.seconds, "window_ticks": kinds,
        "traced_ticks": [t.kind for t in ctx.ticks],
        "window_requests": len(win.reqs),
        "retired_in_window": sum(1 for r in win.every_req if r.retired
                                 and win.start <= r.retired_at <= win.end),
        "drain_s": max(0.0, win.stop - win.end),
        "lateness_p50_s": late[len(late) // 2], "lateness_max_s": late[-1],
        "compiles_before": ctx.compiles_before,
        "compiles_after": ctx.compiles_after,
        "compiles_in_window": ctx.compiles_in_window,
        "tick_max_s": max((t.t1 - t.t0 for t in win.ticks), default=0.0),
        "checked_requests": n_req, "checked_tokens": n_tok,
        "reference_s": ref_s,
    }
    print("bench " + json.dumps(report), flush=True)
    if out_dir:
        od = Path(out_dir)
        od.mkdir(parents=True, exist_ok=True)
        with open(od / "requests.jsonl", "w") as f:
            for r in win.every_req:
                f.write(json.dumps(r.__dict__) + "\n")
        with open(od / "ticks.jsonl", "w") as f:
            for t in win.every_tick:
                f.write(json.dumps({"t0": t.t0, "t1": t.t1, "kind": t.kind,
                                    "n_live": t.n_live, "tokens": t.tokens,
                                    "chunk": t.chunk, "prefill": t.prefill,
                                    "traced": t.traced}) + "\n")
        (od / "report.json").write_text(json.dumps(report, indent=1))
    result = {"correct": correct, "attempted": len(win.reqs),
              "failed": failed, "metrics": metrics, "device": ctx.device}
    if ctx.breakdown is not None:
        result["breakdown"] = ctx.breakdown
    if control:
        ok, cmp = judge(gaps["control"], n_req, limit)
        result["control"] = {"correct": ok, "compared": cmp}
        print(f"control logit_gap {gaps['control']!r} limit {limit!r} -> "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    result["compared"] = compared
    print(f"compared logit_gap {gaps['program']!r} limit {limit!r} -> "
          f"{'ok' if correct else 'FAILED'}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for per-request and per-tick records")
    ap.add_argument("--keep-trace", default=None,
                    help="write the reduced trace events to this JSON file")
    args = ap.parse_args(argv)
    manifest = load_manifest()
    wl, _ = cell(manifest, args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(wl["chips"]):
        print(f"bench: needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    out = args.out or str(ROOT / ".bench_out" / args.workload
                          / f"s{args.seed}.t{args.trace}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), out_dir=out,
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
