"""Request router for disaggregated serving: one front door over a
prefill engine and a decode engine.

Two transports, one protocol:

* :class:`Router` — both engines in THIS process.  Deterministic
  round-robin: import ready spans into free decode slots, prefill the
  next queued request, tick the decode fleet, repeat.  Decode tick
  wall-clock is recorded SEPARATELY from prefill work (``
  decode_tick_times``) — that separation is the measurement the
  disaggregated ``serve_bench`` A/B reports: a prompt flood lands on the
  prefill engine, never inside the decode fleet's fused tick.
* :func:`run_disaggregated` — the same protocol over TWO host processes
  (stdlib ``multiprocessing`` spawn + pipes, ``PageSpan.to_bytes`` as
  the wire format).  Each worker rebuilds its model from the arch name
  and its scheduler from ``ServeConfig`` JSON — the payoff of making the
  config serializable (``serving/config.py``).

Per-request semantics match the combined scheduler: the oversize
reject/truncate/raise policy runs prefill-side at submission, rejected
requests come back as ``RequestResult(finish_reason="rejected")`` under
the ROUTER's rid and submit time, finished requests surface the decode
scheduler's own results.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from repro.serving.config import ServeConfig
from repro.serving.scheduler import RequestResult
from repro.serving.workers import DecodeEngine, PageSpan, PrefillEngine


class Router:
    """In-process disaggregated router: submit like the scheduler, run
    to completion, get per-request results in rid order."""

    def __init__(self, cfg, params, config: ServeConfig, *, mesh=None,
                 span_backlog: int = 4):
        self.config = config
        self.prefill = PrefillEngine(cfg, params, config, mesh=mesh)
        self.decode = DecodeEngine(cfg, params, config, mesh=mesh)
        # prefilled spans waiting for a decode slot; bounding the backlog
        # keeps the prefill engine from racing arbitrarily far ahead of
        # the decode fleet (each span pins host copies of its pages)
        self.span_backlog = max(1, int(span_backlog))
        self._queue: deque = deque()
        self._spans: deque = deque()
        self._results: Dict[int, RequestResult] = {}
        self._next_rid = 0
        #: decode-fleet tick wall-clock, prefill work excluded — the
        #: isolation metric of the disaggregated serve_bench A/B
        self.decode_tick_times: List[float] = []

    def submit(self, prompt, max_new: int,
               eos_id: Optional[int] = None) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, np.asarray(prompt, np.int32), int(max_new),
                            eos_id, time.perf_counter()))
        return rid

    # ------------------------------------------------------------ drive
    def _admit_ready_spans(self) -> bool:
        progressed = False
        while self._spans:
            rid, span, t = self._spans[0]
            status = self.decode.admit(span, rid, t)
            if status in ("full", "wait"):
                break
            self._spans.popleft()
            progressed = True            # "ok", or "drop" (result recorded)
        return progressed

    def _prefill_next(self) -> bool:
        if not self._queue or len(self._spans) >= self.span_backlog:
            return False
        rid, prompt, max_new, eos_id, t = self._queue.popleft()
        span, rejected = self.prefill.prefill(prompt, max_new, eos_id)
        if rejected is not None:
            # re-stamp with the router's identity: the prefill scheduler
            # assigned its own internal rid and submit time
            self._results[rid] = dataclasses.replace(
                rejected, rid=rid, submit_time=t)
        else:
            self._spans.append((rid, span, t))
        return True

    def _tick_decode(self) -> bool:
        if not self.decode.active:
            return False
        t0 = time.perf_counter()
        self.decode.step()
        self.decode_tick_times.append(time.perf_counter() - t0)
        self._results.update(self.decode.drain_results())
        return True

    def step(self) -> bool:
        """One router round; False when no sub-step made progress."""
        progressed = self._admit_ready_spans()
        progressed |= self._prefill_next()
        progressed |= self._tick_decode()
        return progressed

    def run(self) -> List[RequestResult]:
        """Drive everything submitted so far to completion; results in
        rid order (matching ``ServeScheduler.run``)."""
        want = self._next_rid
        while self._queue or self._spans or self.decode.active:
            if not self.step():
                stuck = [rid for rid, _, _ in self._spans]
                raise RuntimeError(
                    f"router wedged: spans for rids {stuck} cannot be "
                    f"imported (decode pool too small for the span?) and "
                    f"no decode work is in flight")
        self._results.update(self.decode.drain_results())
        return [self._results.pop(rid) for rid in range(want)
                if rid in self._results]


# ---------------------------------------------------------------------------
# two-process transport
# ---------------------------------------------------------------------------

def _worker_main(conn, role: str, spec: dict) -> None:
    """Worker process entry (spawn target — must be importable): rebuild
    the model from the arch name and the engine from ServeConfig JSON,
    then serve the parent's RPC loop over the pipe.  Workers run on the
    CPU backend: a TPU chip belongs to one process (DESIGN.md
    §Disaggregated serving)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from repro.configs import get_config, get_smoke
    from repro.models import init_params

    cfg = (get_smoke(spec["arch"]) if spec["smoke"]
           else get_config(spec["arch"]))
    if spec.get("f32"):
        import jax.numpy as jnp
        cfg = cfg.replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(spec["seed"]), cfg)
    if spec.get("quant"):
        from repro.models.quantize import quantize_model_params
        params = quantize_model_params(cfg, params)
    config = ServeConfig.from_json(spec["config_json"])

    if role == "prefill":
        eng = PrefillEngine(cfg, params, config)
        while True:
            msg = conn.recv()
            if msg[0] == "stop":
                break
            _, rid, prompt, max_new, eos_id = msg
            span, rejected = eng.prefill(np.asarray(prompt, np.int32),
                                         max_new, eos_id)
            if rejected is not None:
                conn.send(("rejected", rid, rejected.error,
                           rejected.prompt_len))
            else:
                conn.send(("span", rid, span.to_bytes()))
    else:
        eng = DecodeEngine(cfg, params, config)
        while True:
            msg = conn.recv()
            if msg[0] == "stop":
                break
            if msg[0] == "admit":
                _, rid, blob, t = msg
                status = eng.admit(PageSpan.from_bytes(blob), rid, t)
                conn.send(("admitted", rid, status))
            elif msg[0] == "tick":
                t0 = time.perf_counter()
                eng.step()
                dt = time.perf_counter() - t0
                done = [(r.rid, list(r.tokens), r.finish_reason,
                         r.prompt_len, r.error)
                        for r in eng.drain_results().values()]
                conn.send(("results", done, eng.active, dt))
    conn.close()


def _recv(conn, proc, what: str, timeout: float):
    if not conn.poll(timeout):
        alive = proc.is_alive()
        raise RuntimeError(f"disaggregated worker timed out waiting for "
                           f"{what} (alive={alive}, "
                           f"exitcode={proc.exitcode})")
    return conn.recv()


def run_disaggregated(trace, *, arch: str, config: ServeConfig,
                      smoke: bool = True, f32: bool = True, seed: int = 0,
                      quant: bool = False, timeout: float = 600.0):
    """Serve ``trace`` (a list of ``(prompt, max_new, eos_id)``) across
    TWO spawned worker processes — prefill and decode — returning
    ``[(rid, tokens, finish_reason, error), ...]`` in rid order.

    The parent never touches jax: it shuttles prompts to the prefill
    worker, ``PageSpan`` byte frames to the decode worker, and ticks the
    decode worker until every admitted request retires.  Also returns the
    decode worker's per-tick wall-clock (the isolation measurement).

    Both workers run on the CPU backend, and a parent that holds a TPU is
    refused: one process owns a chip.  On the chip, serve in one process
    through :class:`Router`.
    """
    import multiprocessing as mp

    from repro.launch.runtime import refuse_if_chip_held

    refuse_if_chip_held("run_disaggregated")

    ctx = mp.get_context("spawn")
    spec = {"arch": arch, "smoke": smoke, "f32": f32, "seed": seed,
            "quant": quant, "config_json": config.to_json()}
    p_parent, p_child = ctx.Pipe()
    d_parent, d_child = ctx.Pipe()
    prefill = ctx.Process(target=_worker_main,
                          args=(p_child, "prefill", spec), daemon=True)
    decode = ctx.Process(target=_worker_main,
                         args=(d_child, "decode", spec), daemon=True)
    prefill.start()
    decode.start()
    results: Dict[int, tuple] = {}
    tick_times: List[float] = []
    in_flight = 0

    def tick_once():
        nonlocal in_flight
        d_parent.send(("tick",))
        _, done, active, dt = _recv(d_parent, decode, "tick", timeout)
        tick_times.append(dt)
        for rid, tokens, reason, plen, err in done:
            results[rid] = (rid, tokens, reason, err)
            in_flight -= 1
        return active

    try:
        for rid, (prompt, max_new, eos_id) in enumerate(trace):
            p_parent.send(("prefill", rid, np.asarray(prompt, np.int32),
                           int(max_new), eos_id))
            kind, _, *payload = _recv(p_parent, prefill, "prefill", timeout)
            if kind == "rejected":
                results[rid] = (rid, [], "rejected", payload[0])
                continue
            blob = payload[0]
            while True:
                d_parent.send(("admit", rid, blob, time.perf_counter()))
                _, _, status = _recv(d_parent, decode, "admit", timeout)
                if status == "ok":
                    in_flight += 1
                    break
                if status == "drop":
                    # the decode engine recorded a rejected result; it
                    # arrives with the next tick's drain like any retire
                    in_flight += 1
                    break
                tick_once()     # "full"/"wait": free a slot by ticking
        while in_flight:
            tick_once()
    finally:
        for conn, proc in ((p_parent, prefill), (d_parent, decode)):
            try:
                conn.send(("stop",))
            except (BrokenPipeError, OSError):
                pass
            proc.join(timeout=30)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=10)
    ordered = [results[rid] for rid in sorted(results)]
    return ordered, tick_times
