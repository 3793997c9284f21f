"""Continuous-batching serve scheduler over a persistent slot-based cache
pool.

The fused decode engine (``serving/engine.py``) runs one rectangular batch
per compiled program — fine for offline eval, wrong for serving: a finished
row idles its slot until the whole batch drains, and every generate
re-allocates its caches.  This module keeps the quantized decode path
*saturated* under sustained multi-request load, the bandwidth-bound regime
where QeiHaN's plane-skipping pays (PAPER §VI; DESIGN.md §Scheduler):

* **Slot pool** — ONE persistent allocation: ``max_slots`` cache rows of
  ``max_len`` each (``init_caches(per_slot=True)``, per-row ``length``).
  Slots are reset by *overwriting*, never re-allocated.
* **Bucketed prefill** — prompts are right-padded to the smallest
  configured bucket, so prefill compiles once per bucket, not once per
  prompt length.  Pad tokens are masked out of the SSM state
  (``valid_len``) and sit causally after every real token for attention.
* **Chunked prefill** (``chunked="auto"|"always"``) — a prompt is split
  into fixed ``chunk_len`` chunks fed straight into the slot pool across
  successive ticks (``engine.make_slot_prefill_chunk``), interleaved with
  decode for the other slots in ONE jitted mixed tick — a long prompt no
  longer stalls every in-flight decode slot for its full prefill, and
  admission is bounded by ``max_len`` instead of ``buckets[-1]``.  The
  chunk slab is ONE compiled shape for every prompt length (vs one prefill
  program per bucket).  ``"auto"`` (the default when enabling) chunks only
  prompts longer than the largest bucket, so every in-bucket prompt keeps
  the bucketed path's bit-exact token guarantee; ``"always"`` chunks
  everything — maximal interleaving, tokens agree with the bucketed path
  to f32-ULP logits (token-equal on every tested seed/arch, asserted in
  tests, but not *guaranteed* bit-equal: chunk-boundary GEMM shapes
  reassociate the same sums — DESIGN.md §Chunked prefill).
* **Tick loop** — ONE jitted program steps *all* slots ``tick_steps``
  greedy tokens at a time (a ``lax.scan`` over ``make_slot_serve_step``);
  host logic between ticks detects EOS / length exhaustion, retires the
  slot and immediately re-fills it from the queue — decode never drains to
  refill the batch.
* **Per-request traffic stats** — with ``with_stats=True`` each tick
  reports the per-step batch-aggregate ``plane_traffic_fraction`` /
  ``element_traffic_fraction``; the scheduler attributes each step's
  fractions to the requests active at that step and reports the per-request
  mean.
* **Paged KV pool** (``paged=True``) — attention KV moves from dense
  per-slot ``(max_len, ...)`` slabs into a shared pool of fixed-size
  pages (``models.model.init_paged_pool``) indexed through host-side
  per-slot page tables (``serving/kvpool.py``): writes scatter at
  (page, offset), reads gather each slot's pages into its dense logical
  view and run the SAME masked einsums — tokens bit-equal to the dense
  scheduler on prefix-free traffic.  Pool exhaustion waits for in-flight
  retirements, or resolves through the ``oversize`` policy when idle.
* **Radix prefix cache** (``prefix_cache=True``) — retired prompts donate
  their whole-page KV blocks to a radix tree keyed on token ids; a new
  request aliases its longest cached prefix (refcounted shared pages,
  partial tail page via copy-on-write) and ingests only the suffix
  through the chunked path — the shared tokens skip prefill compute AND
  cache writes (DESIGN.md §Paged KV + prefix cache).  SSM/hybrid models
  reuse hits via bounded-LRU state snapshots at page-aligned boundaries.
* **Mesh-native** — pass ``mesh=`` and the slot pool is allocated
  device-sharded exactly once (batch on ``data``, kv-seq / ssm-heads on
  ``model``, per-slot ``(B,)`` lengths on ``data`` —
  ``launch.shardings.serve_shardings``), the prefill / write / tick
  programs are jitted with explicit ``in_shardings`` / ``out_shardings``,
  and admission / retirement keep touching only host-side metadata (the
  ``active`` bitmap and per-slot token lists) — the tick loop performs no
  cross-device gathers beyond the (B, tick_steps) token array every tick
  already syncs to host.  Scheduler tokens are bit-equal to the
  single-device scheduler (tests/test_serve_sharded.py).

Token outputs are exactly the per-request ``greedy_generate`` outputs
(property-tested): same prefill math (padding contributes exact zeros),
same masked decode attention, same greedy sampling.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import ModelConfig, init_caches, init_paged_pool
from repro.serving import engine
from repro.serving.config import ServeConfig
from repro.serving.kvpool import (TRASH_PAGE, PagePool, RadixCache,
                                  blocks_for_tokens)

# the legacy keyword surface: exactly the ServeConfig fields minus
# mesh_spec (the old signature took a live mesh OBJECT, which stays a
# first-class scheduler argument — device binding is process-local)
_LEGACY_KWARGS = frozenset(
    f.name for f in dataclasses.fields(ServeConfig)) - {"mesh_spec"}


def bucket_for(length: int, buckets: Sequence[int]) -> int:
    """Smallest configured bucket that holds ``length`` real tokens."""
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(f"prompt length {length} exceeds the largest prefill "
                     f"bucket {max(buckets)}")


def round_pool_len(base: int, chunk_len: int) -> int:
    """Smallest multiple of ``chunk_len`` >= ``base`` — the ``max_len`` a
    chunked :class:`ServeScheduler` accepts (the constructor validates
    rather than silently rounding, so sizing stays an explicit caller
    decision; every CLI/bench derives its pool through this helper)."""
    return -(-int(base) // int(chunk_len)) * int(chunk_len)


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    prompt: np.ndarray                  # (L,) int32 token ids
    max_new: int
    eos_id: Optional[int] = None
    submit_time: float = float("nan")   # time.perf_counter() at submit()


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    tokens: List[int]
    finish_reason: str                  # "eos" | "length" | "rejected"
    admitted_tick: int                  # -1 for rejected requests
    finished_tick: int
    # per-request mean of the per-step batch-aggregate traffic fractions
    # over the steps this request was active (nan without stats)
    plane_traffic_fraction: float = float("nan")
    element_traffic_fraction: float = float("nan")
    error: Optional[str] = None         # why a "rejected" request never ran
    # wall-clock marks on one time.perf_counter() clock — latency reporting
    # (benchmarks/serve_bench.py): TTFT = first_token_time - submit_time
    # (queue wait + prefill), e2e = finish_time - submit_time
    submit_time: float = float("nan")
    first_token_time: float = float("nan")
    finish_time: float = float("nan")


@dataclasses.dataclass
class _Slot:
    req: Request
    admitted_tick: int
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str = ""
    frac_sums: List[float] = dataclasses.field(
        default_factory=lambda: [0.0, 0.0])
    frac_steps: int = 0
    # chunked-prefill state machine: an admitted slot is "prefill" until its
    # last chunk lands (bucketed admissions enter directly at "decode"),
    # then decodes until EOS/length retires it
    phase: str = "decode"               # "prefill" | "decode"
    prefill_pos: int = 0                # prompt tokens ingested so far
    first_token_time: float = float("nan")
    # paged mode: every page this slot holds a reference on (fresh allocs,
    # shared prefix pages, COW copies), the prefix-hit length it was
    # admitted with, and the SSM/conv state snapshot at the cacheable
    # prompt boundary (hybrid models, captured opportunistically)
    pages: List[int] = dataclasses.field(default_factory=list)
    hit_len: int = 0
    snapshot: Optional[tuple] = None


class ServeScheduler:
    """Continuous-batching scheduler: admit -> tick -> retire -> re-fill.

    Greedy decoding only (per-request temperatures would break the shared
    batched argmax; the fused single-batch engine covers sampling).  Audio /
    vision frontends are out of scope — they prefill from embeddings, not
    token ids.

    Usage::

        sc = ServeConfig(max_slots=8, max_len=256)
        sched = ServeScheduler(cfg, params, sc)
        for p in prompts:
            sched.submit(p, max_new=32, eos_id=2)
        results = sched.run()          # List[RequestResult], rid order

    The legacy keyword form (``ServeScheduler(cfg, params, max_slots=8,
    ...)``) still works — it routes through ``ServeConfig`` and emits a
    ``DeprecationWarning``; every knob below is a ``ServeConfig`` field.

    ``chunked="auto"`` (or ``True``) adds chunked prefill: prompts longer
    than the largest bucket — rejected outright without it — are ingested
    ``chunk_len`` tokens per tick (default: the smallest bucket),
    interleaved with decode for the other slots; ``chunked="always"``
    chunks every prompt (maximal interleaving / bounded per-tick latency).
    ``max_len`` must be a multiple of ``chunk_len``.

    ``paged=True`` swaps the dense per-slot KV slabs for the shared page
    pool (``page_len`` tokens per page, ``n_pages`` total — default sizes
    every slot fully resident plus prefix-cache headroom; ``max_len`` must
    be a multiple of ``page_len``); ``prefix_cache=True`` (requires paged)
    adds radix-tree prefix reuse with ``min_prefix_hit`` (default
    ``page_len``) as the smallest hit worth taking and ``snapshot_limit``
    bounding the SSM-state snapshots hybrid models need per hit.

    ``attn_kernel=True`` (or ``"pallas"``; requires ``paged``) routes the
    decode read through the fused paged-attention kernel
    (``kernels/paged_attention``): the kernel walks the page tables
    directly instead of gathering ``pool[table]`` into the dense padded
    view, and ``attn_splits`` partitions the KV page axis flash-decode
    style (partial softmax statistics merged at the end).  Tokens are
    equal to the dense-gather scheduler on every tested seed/arch
    (asserted in tests/test_paged_attention.py); logits agree to f32-ULP
    softmax reassociation — same bar as chunked-vs-bucketed prefill.
    """

    def __init__(self, cfg: ModelConfig, params,
                 config: Optional[ServeConfig] = None, *,
                 mesh=None, **legacy):
        """Build from a :class:`ServeConfig` (canonical form) or the
        legacy keyword surface (deprecated shim: same defaults, same
        validation — it routes through ``ServeConfig`` — byte-for-byte
        the same scheduler, plus a ``DeprecationWarning``).  ``mesh=``
        stays a first-class argument either way: a live mesh is
        process-local device BINDING, not configuration; when only
        ``config.mesh_spec`` is set, it resolves here via
        ``make_serve_mesh``."""
        if cfg.frontend != "none":
            raise ValueError("ServeScheduler serves token-id models only "
                             f"(frontend={cfg.frontend!r})")
        if config is None:
            unknown = sorted(set(legacy) - _LEGACY_KWARGS)
            if unknown:
                raise TypeError(f"ServeScheduler: unexpected keyword "
                                f"arguments {unknown}")
            if legacy:
                warnings.warn(
                    "ServeScheduler(cfg, params, **kwargs) is deprecated: "
                    "build a serving.ServeConfig and pass it as the third "
                    "argument — ServeScheduler(cfg, params, serve_config)",
                    DeprecationWarning, stacklevel=2)
            config = ServeConfig(**legacy)
        elif legacy:
            raise TypeError(f"ServeScheduler: pass EITHER a ServeConfig or "
                            f"legacy keyword arguments, not both (got a "
                            f"config plus {sorted(legacy)})")
        if not isinstance(config, ServeConfig):
            raise TypeError(f"ServeScheduler: config must be a ServeConfig,"
                            f" got {type(config).__name__}")
        if mesh is None:
            mesh = config.make_mesh()
        self.serve_config = config

        # unpack the validated knobs into locals (the builder below) and
        # the long-standing public attributes (benches/tests read these)
        max_slots = config.max_slots
        max_len = config.max_len
        buckets = config.buckets
        quant = config.quant
        with_stats = config.with_stats
        tick_steps = config.tick_steps
        chunk_len = config.chunk_len
        paged = config.paged
        page_len = config.page_len
        prefix_cache = config.prefix_cache
        needs_chunk_programs = config.needs_chunk_programs
        attn_kernel = config.attn_kernel
        kv_quant = config.kv_quant
        kv_bits = config.kv_bits
        if paged:
            max_blocks = config.max_blocks
            n_pages = config.resolved_n_pages(mesh)
            # NB a pool SMALLER than one full slot (max_blocks + 1 pages) is
            # legal: requests that can never fit it resolve through the
            # oversize policy at admission (reject/truncate/raise), so an
            # under-provisioned pool degrades per-request, never crashes
        if attn_kernel != "off":
            # the flag rides the config: every compiled program built below
            # (tick / chunk / mixed) picks up the kernel dispatch through
            # models.attention, with no engine-level plumbing
            cfg = cfg.replace(paged_attn_kernel=attn_kernel,
                              paged_attn_splits=config.attn_splits)
        if kv_quant:
            # like attn_kernel, the quantized-pool mode rides the config:
            # init_paged_pool emits the codes/scale/tail leaves and
            # models.attention dispatches the quantize-on-write path
            cfg = cfg.replace(kv_quant=True, kv_bits=kv_bits)
        self.kv_quant = kv_quant
        self.kv_bits = kv_bits
        self.attn_kernel = attn_kernel
        self.attn_splits = config.attn_splits
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.buckets = buckets
        self.quant = quant
        self.with_stats = with_stats
        self.tick_steps = tick_steps
        self.mesh = mesh
        self.oversize = config.oversize
        self.chunked = config.chunked
        self.chunk_len = chunk_len
        self.paged = paged
        self.page_len = page_len if paged else 0
        self.prefix_cache = prefix_cache
        self._has_ssm = any(k.split("_")[0] == "mamba" for k in cfg.pattern)
        self.min_prefix_hit = config.min_prefix_hit
        self._needs_chunk_programs = needs_chunk_programs
        # disaggregation hook (serving/workers.py PrefillEngine): hold
        # EVERY finishing chunk row out of the same-tick decode scan, so
        # prefill-only ingestion never generates a token — the cut point
        # between the prefill and decode engines is post-chunk, pre-decode
        self._defer_decode = False
        # set to {} to record each request's first-token logits (rid ->
        # (V,) array) where they sit in the slot row before the tick that
        # emits the token: bucketed admissions and deferred chunks.  A
        # chunk that finishes and decodes inside one program is not seen.
        self.first_logits: Optional[Dict[int, np.ndarray]] = None

        # the generate-program LRU serves the per-request parity / baseline
        # path (greedy_generate): size it so one program per (bucket x
        # float/quant x eos on/off) variant fits without evicting anything.
        # NB the LRU is process-global: the default sizing only ever GROWS
        # it; pass an explicit generate_cache_size only if this scheduler is
        # the sole greedy_generate consumer in the process (shrinking evicts
        # other callers' live programs).
        generate_cache_size = config.generate_cache_size
        if generate_cache_size is None:
            generate_cache_size = max(engine.generate_fn.maxsize,
                                      4 * len(buckets) + 16)
        engine.set_generate_cache_size(generate_cache_size)

        # --- persistent pool (allocated exactly once) ----------------------
        if paged:
            self.max_blocks = max_blocks = max_len // page_len
            self.n_pages = n_pages
            self._pool = init_paged_pool(cfg, max_slots, max_len, n_pages,
                                         page_len, dtype=cfg.dtype)
            self._pages = PagePool(n_pages, page_len)
            # host-side page tables, one row per slot; entry 0 = trash page
            self._table = np.zeros((max_slots, max_blocks), np.int32)
            self._radix = (RadixCache(self._pages,
                                      snapshot_limit=config.snapshot_limit)
                           if prefix_cache else None)
            # prefix-cache observability (serve_bench --prefix-trace):
            # cached_tokens prompt tokens were served straight from shared
            # pages — their prefill compute AND cache writes were skipped
            self.prefix_stats = {"prompt_tokens": 0, "cached_tokens": 0,
                                 "prefill_tokens": 0,
                                 # pool-footprint accounting (serve_bench
                                 # --kv-quant): pages each admitted slot
                                 # held, admissions counted
                                 "pages_held": 0, "admitted": 0}
        else:
            self._pool = init_caches(cfg, max_slots, max_len, dtype=cfg.dtype,
                                     per_slot=True)
            self._pages = self._radix = None
        self._logits = jnp.zeros((max_slots, cfg.vocab_size), cfg.dtype)
        self._active = np.zeros((max_slots,), bool)
        self._slots: List[Optional[_Slot]] = [None] * max_slots

        self._queue: Deque[Request] = deque()
        self._results: Dict[int, RequestResult] = {}
        self._next_rid = 0
        self._tick_count = 0

        # sharding specs: pool batch on `data`, kv-seq/ssm-heads on `model`,
        # per-slot (B,) lengths on `data`; params get the TP rules (incl.
        # packed bit-planes).  The pool is device-put sharded ONCE here —
        # every later tick donates it in place.
        if mesh is not None:
            from repro.launch.shardings import serve_shardings
            spec = serve_shardings(mesh, params, self._pool, batch=max_slots,
                                   paged=self.paged)
            rep = spec["replicated"]
            self.params = params = jax.device_put(params, spec["params"])
            self._pool = jax.device_put(self._pool, spec["caches"])
            self._logits = jax.device_put(self._logits, spec["logits"])
            # batch-1 prefill outputs replicate (a 1-row batch divides no
            # data axis); the slot write scatters them into the sharded pool.
            # Built from the DENSE 1-row cache tree, not the pool — under
            # kv_quant the pool's layer dicts carry codes/scale/tail leaves
            # the prefill output doesn't have
            cache1_sh = jax.tree.map(
                lambda _: rep,
                jax.eval_shape(lambda: init_caches(cfg, 1, max_len,
                                                   dtype=cfg.dtype)))
            # paged mode threads the host-built (B, n_blocks) page table
            # through every device program; its rows ride the slot batch
            # sharding like the token slab
            pt = (spec["tokens"],) if self.paged else ()
            sh = dict(
                prefill_in=(spec["params"], rep, rep),
                prefill_out=(rep, cache1_sh),
                write_in=(spec["caches"], cache1_sh, spec["logits"], rep,
                          rep) + ((rep, rep) if self.paged else ()),
                write_out=(spec["caches"], spec["logits"]),
                tick_in=(spec["params"], spec["caches"], spec["logits"],
                         spec["active"]) + pt,
                tick_out=(spec["logits"], spec["caches"], rep, rep),
                # chunked prefill: the (B, chunk_len) token slab rides the
                # per-slot row sharding (batch on `data`, like the pool);
                # the (B,) valid/fresh/finishing flag vectors ride `active`'s
                chunk_in=(spec["params"], spec["caches"], spec["logits"],
                          spec["tokens"], spec["active"], spec["active"],
                          spec["active"]) + pt,
                chunk_out=(spec["logits"], spec["caches"], rep),
                mixed_in=(spec["params"], spec["caches"], spec["logits"],
                          spec["active"], spec["tokens"], spec["active"],
                          spec["active"], spec["active"]) + pt,
                mixed_out=(spec["logits"], spec["caches"], rep, rep, rep),
                cow_in=(spec["caches"], rep, rep),
                cow_out=spec["caches"],
                snap_in=(spec["caches"], rep),
                snap_out=rep,
                # kv_quant appends the scalar tail-page id operand
                hit_in=(spec["caches"], rep, rep)
                + ((rep,) if self.kv_quant else ()),
                hit_out=spec["caches"],
                hit_snap_in=(spec["caches"], rep, rep, rep)
                + ((rep,) if self.kv_quant else ()),
            )
        else:
            sh = collections.defaultdict(lambda: None)

        # --- compiled programs --------------------------------------------
        # prefill: ONE jit wrapper; it retraces per *bucket* shape only —
        # the compiled-program count is bounded by len(buckets)
        slot_prefill = engine.make_slot_prefill(cfg, quant)

        def prefill(params, prompt, true_len):
            caches = init_caches(cfg, 1, max_len, dtype=cfg.dtype)
            return slot_prefill(params, prompt, true_len, caches)

        self._prefill = engine.jit_sharded(
            prefill, mesh, in_shardings=sh["prefill_in"],
            out_shardings=sh["prefill_out"])

        # slot write: shape-independent of the bucket -> exactly one program.
        # The paged variant scatters the freshly-prefilled dense 1-row cache
        # into the slot's pages — positions < true_len land at (page_row[
        # p // page_len], p % page_len), the rest go to the trash page —
        # while SSM/conv state and logits keep the dense per-slot write.
        def write_slot(pool, slot_cache, pool_logits, slot_logits, i,
                      page_row=None, true_len=None):
            if self.paged:
                pl = self.page_len
                pos = jnp.arange(max_len, dtype=jnp.int32)
                valid = pos < true_len
                page = jnp.where(valid, page_row[pos // pl], TRASH_PAGE)
                off = jnp.where(valid, pos % pl, 0)

                def quant_write(c_pool, c_slot):
                    # quantize the freshly-prefilled dense slab page-wise:
                    # codes under each page's first-row scale, the scale
                    # entries themselves (valid pages only — dead pages
                    # redirect to the trash entry), and the newest two
                    # pages dense into slot i's tail ring (older rows and
                    # pad rows hit the junk bin, row 2*page_len)
                    from repro.core.logquant import (quantize_page_codes,
                                                     scale_exponent)
                    nb_ = max_len // pl
                    ring = 2 * pl
                    bv = jnp.arange(nb_, dtype=jnp.int32) * pl < true_len
                    sp = jnp.where(bv, page_row, TRASH_PAGE)
                    in_ring = valid & (pos >= true_len - ring)
                    toff = jnp.where(in_ring, pos % ring, ring)
                    out = {}
                    for k in ("k", "v"):
                        x = c_slot[k][:, 0].astype(jnp.float32)
                        xb = x.reshape(x.shape[0], nb_, pl, *x.shape[2:])
                        se = scale_exponent(xb[:, :, 0], axis=-1)
                        qc = quantize_page_codes(
                            xb, se[:, :, None, :, None], self.kv_bits)
                        qc = qc.reshape(x.shape[0], max_len, *x.shape[2:])
                        codes = c_pool[f"{k}_codes"]
                        out[f"{k}_codes"] = codes.at[:, page, off].set(
                            qc.astype(codes.dtype))
                        out[f"{k}_scale"] = c_pool[f"{k}_scale"].at[
                            :, sp].set(se)
                        tail = c_pool[f"{k}_tail"]
                        out[f"{k}_tail"] = tail.at[:, i, toff].set(
                            c_slot[k][:, 0].astype(tail.dtype))
                    return out

                layers = []
                for c_pool, c_slot in zip(pool["layers"],
                                          slot_cache["layers"]):
                    if "ssm" in c_pool:
                        layers.append({k: jax.lax.dynamic_update_slice_in_dim(
                            c_pool[k], c_slot[k].astype(c_pool[k].dtype),
                            i, axis=1) for k in c_pool})
                    elif self.kv_quant:
                        layers.append(quant_write(c_pool, c_slot))
                    else:
                        layers.append({k: c_pool[k].at[:, page, off].set(
                            c_slot[k][:, 0].astype(c_pool[k].dtype))
                            for k in ("k", "v")})
                layers = tuple(layers)
                length = jax.lax.dynamic_update_slice_in_dim(
                    pool["length"], true_len[None].astype(jnp.int32),
                    i, axis=0)
            else:
                layers = jax.tree.map(
                    lambda p, s: jax.lax.dynamic_update_slice_in_dim(
                        p, s.astype(p.dtype), i, axis=1),
                    pool["layers"], slot_cache["layers"])
                length = jax.lax.dynamic_update_slice_in_dim(
                    pool["length"], slot_cache["length"].astype(jnp.int32),
                    i, axis=0)
            logits = jax.lax.dynamic_update_slice_in_dim(
                pool_logits, slot_logits.astype(pool_logits.dtype),
                i, axis=0)
            return {"layers": layers, "length": length}, logits

        if self.paged:
            def write_slot_paged(pool, slot_cache, pool_logits, slot_logits,
                                 i, page_row, true_len):
                return write_slot(pool, slot_cache, pool_logits, slot_logits,
                                  i, page_row, true_len)
            self._write = engine.jit_sharded(
                write_slot_paged, mesh, in_shardings=sh["write_in"],
                out_shardings=sh["write_out"], donate_argnums=(0, 2))
        else:
            self._write = engine.jit_sharded(
                write_slot, mesh, in_shardings=sh["write_in"],
                out_shardings=sh["write_out"], donate_argnums=(0, 2))

        # tick: scan tick_steps slot-masked greedy steps -> one program.
        # tick_body is shared verbatim by the standalone tick and the mixed
        # chunk+decode program, so the decode math is one code path.  In
        # paged mode every program additionally takes the host-built page
        # table (constant within a tick: pages are allocated at admission).
        step = engine.make_slot_serve_step(cfg, quant, with_stats=with_stats,
                                           paged=self.paged)

        def tick_body(params, pool, logits, active, page_table=None):
            extra = (page_table,) if self.paged else ()

            def body(carry, _):
                lg, cs = carry
                tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                out = step(params, cs, tok[:, None], active, *extra)
                if with_stats:
                    lg, cs, stats = out
                    frac = jnp.stack([stats["plane_traffic_fraction"],
                                      stats["element_traffic_fraction"]])
                else:
                    lg, cs = out
                    frac = jnp.zeros((2,), jnp.float32)
                return (lg, cs), (tok, frac)

            (lg, cs), (toks, fracs) = jax.lax.scan(
                body, (logits, pool), None, length=tick_steps)
            return lg, cs, jnp.swapaxes(toks, 0, 1), fracs

        if self.paged:
            def tick_paged(params, pool, logits, active, page_table):
                return tick_body(params, pool, logits, active, page_table)
            self._tick = engine.jit_sharded(
                tick_paged, mesh, in_shardings=sh["tick_in"],
                out_shardings=sh["tick_out"], donate_argnums=(1,))
        else:
            self._tick = engine.jit_sharded(
                tick_body, mesh, in_shardings=sh["tick_in"],
                out_shardings=sh["tick_out"], donate_argnums=(1,))

        # chunked prefill: ONE fixed (B, chunk_len) slab shape regardless of
        # prompt length — the chunk-only program covers prefill-only ticks,
        # the mixed program runs chunk ingestion AND the decode scan in one
        # jitted dispatch so decode never drains while a long prompt ingests
        self._chunk = self._mixed = None
        if self._needs_chunk_programs:
            chunk_step = engine.make_slot_prefill_chunk(
                cfg, quant, with_stats=with_stats, paged=self.paged)

            def chunk_body(params, pool, logits, tokens, valid, fresh,
                           finishing, page_table=None):
                extra = (page_table,) if self.paged else ()
                out = chunk_step(params, pool, logits, tokens, valid, fresh,
                                 finishing, *extra)
                if with_stats:
                    lg, cs, stats = out
                    cfrac = jnp.stack([stats["plane_traffic_fraction"],
                                       stats["element_traffic_fraction"]])
                else:
                    lg, cs = out
                    cfrac = jnp.zeros((2,), jnp.float32)
                return lg, cs, cfrac

            def mixed_tick(params, pool, logits, active, tokens, valid,
                           fresh, finishing, page_table=None):
                lg, cs, cfrac = chunk_body(params, pool, logits, tokens,
                                           valid, fresh, finishing,
                                           page_table)
                lg, cs, toks, fracs = tick_body(params, cs, lg, active,
                                                page_table)
                return lg, cs, toks, fracs, cfrac

            if self.paged:
                def chunk_paged(params, pool, logits, tokens, valid, fresh,
                                finishing, page_table):
                    return chunk_body(params, pool, logits, tokens, valid,
                                      fresh, finishing, page_table)

                def mixed_paged(params, pool, logits, active, tokens, valid,
                                fresh, finishing, page_table):
                    return mixed_tick(params, pool, logits, active, tokens,
                                      valid, fresh, finishing, page_table)
                self._chunk = engine.jit_sharded(
                    chunk_paged, mesh, in_shardings=sh["chunk_in"],
                    out_shardings=sh["chunk_out"], donate_argnums=(1,))
                self._mixed = engine.jit_sharded(
                    mixed_paged, mesh, in_shardings=sh["mixed_in"],
                    out_shardings=sh["mixed_out"], donate_argnums=(1,))
            else:
                self._chunk = engine.jit_sharded(
                    chunk_body, mesh, in_shardings=sh["chunk_in"],
                    out_shardings=sh["chunk_out"], donate_argnums=(1,))
                self._mixed = engine.jit_sharded(
                    mixed_tick, mesh, in_shardings=sh["mixed_in"],
                    out_shardings=sh["mixed_out"], donate_argnums=(1,))

        # paged-only device helpers: copy-on-write page duplication (the
        # partially-matching tail page of a prefix hit is copied into a page
        # the slot owns exclusively before any write can touch it), the
        # SSM-state snapshot gather (prefix-cache donors on hybrid models),
        # and the prefix-hit admission write (length + snapshot restore).
        self._cow = self._snap = None
        if self.paged:
            # COW must copy a quantized page's codes AND its scale entry
            # together — codes are meaningless under another page's scale;
            # the per-slot tail rings aren't page-addressed and pass through
            cow_keys = (("k_codes", "v_codes", "k_scale", "v_scale")
                        if self.kv_quant else ("k", "v"))

            def cow_pages(pool, src, dst):
                layers = []
                for c in pool["layers"]:
                    if "ssm" in c:
                        layers.append(c)
                    else:
                        nc = dict(c)
                        nc.update({k: c[k].at[:, dst].set(
                            jax.lax.dynamic_slice_in_dim(
                                c[k], src, 1, axis=1)[:, 0])
                            for k in cow_keys})
                        layers.append(nc)
                return {"layers": tuple(layers), "length": pool["length"]}

            self._cow = engine.jit_sharded(
                cow_pages, mesh, in_shardings=sh["cow_in"],
                out_shardings=sh["cow_out"], donate_argnums=(0,))

            def snap_slot(pool, i):
                out = []
                for c in pool["layers"]:
                    if "ssm" in c:
                        out.append({k: jax.lax.dynamic_slice_in_dim(
                            c[k], i, 1, axis=1) for k in c})
                return tuple(out)

            self._snap = engine.jit_sharded(
                snap_slot, mesh, in_shardings=sh["snap_in"],
                out_shardings=sh["snap_out"])

            def admit_hit(pool, i, hit_len, snaps=None, tail_pg=None):
                length = jax.lax.dynamic_update_slice_in_dim(
                    pool["length"], hit_len[None].astype(jnp.int32),
                    i, axis=0)
                pl = self.page_len
                tb = jnp.maximum(hit_len - 1, 0) // pl
                half = (tb % 2) * pl
                layers = []
                si = 0
                for c in pool["layers"]:
                    if "ssm" in c and snaps is not None:
                        sn = snaps[si]
                        si += 1
                        layers.append(
                            {k: jax.lax.dynamic_update_slice_in_dim(
                                c[k], sn[k].astype(c[k].dtype), i, axis=1)
                             for k in c})
                    elif "k_codes" in c and tail_pg is not None:
                        # restore slot i's tail ring from the hit's tail
                        # page: the overlay reads the newest page from the
                        # ring, and the previous occupant's rows are stale
                        # junk.  Dequantized rows are exactly what every
                        # later read of these positions would decode from
                        # the pool, so the quantized-read semantics are
                        # unchanged — only the ring-vs-pool routing is.
                        from repro.core.logquant import dequantize_page_codes
                        nc = dict(c)
                        for k in ("k", "v"):
                            pg = jax.lax.dynamic_slice_in_dim(
                                c[f"{k}_codes"], tail_pg, 1, axis=1)[:, 0]
                            se = jax.lax.dynamic_slice_in_dim(
                                c[f"{k}_scale"], tail_pg, 1, axis=1)
                            rows = dequantize_page_codes(
                                pg, se[..., None], self.kv_bits,
                                c[f"{k}_tail"].dtype)
                            nc[f"{k}_tail"] = jax.lax.dynamic_update_slice(
                                c[f"{k}_tail"], rows[:, None],
                                (0, i, half, 0, 0))
                        layers.append(nc)
                    else:
                        layers.append(c)
                return {"layers": tuple(layers), "length": length}

            if self.kv_quant:
                self._admit_hit_plain = engine.jit_sharded(
                    lambda pool, i, hit_len, tail_pg: admit_hit(
                        pool, i, hit_len, tail_pg=tail_pg),
                    mesh, in_shardings=sh["hit_in"],
                    out_shardings=sh["hit_out"], donate_argnums=(0,))
                self._admit_hit_snap = engine.jit_sharded(
                    lambda pool, i, hit_len, snaps, tail_pg: admit_hit(
                        pool, i, hit_len, snaps, tail_pg),
                    mesh, in_shardings=sh["hit_snap_in"],
                    out_shardings=sh["hit_out"], donate_argnums=(0,))
            else:
                self._admit_hit_plain = engine.jit_sharded(
                    lambda pool, i, hit_len: admit_hit(pool, i, hit_len),
                    mesh, in_shardings=sh["hit_in"],
                    out_shardings=sh["hit_out"], donate_argnums=(0,))
                self._admit_hit_snap = engine.jit_sharded(
                    lambda pool, i, hit_len, snaps: admit_hit(
                        pool, i, hit_len, snaps),
                    mesh, in_shardings=sh["hit_snap_in"],
                    out_shardings=sh["hit_out"], donate_argnums=(0,))

    # ------------------------------------------------------------------ API

    def submit(self, prompt, max_new: int, eos_id: Optional[int] = None) -> int:
        """Queue one request; returns its rid (results come back in rid
        order from :meth:`run`).

        A prompt that exceeds the admission bound (without chunking: the
        largest prefill bucket; with ``chunked="auto"|"always"``: only the
        slot capacity — chunking removes the bucket ceiling) or whose
        prompt + ``max_new`` overflows the slot capacity is handled per the
        ``oversize`` policy: ``"reject"`` (default) records a per-request
        ``RequestResult(finish_reason="rejected", error=...)`` and leaves
        every queued/in-flight request untouched — submission during a live
        serve loop must never abort the loop; ``"truncate"`` keeps the most
        recent tokens that fit; ``"raise"`` restores the historical
        ``ValueError`` (batch scripts that want loud failures).  Empty
        prompts and ``max_new < 1`` are caller bugs and always raise.
        """
        now = time.perf_counter()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if self.chunked == "off":
            fit = min(self.buckets[-1], self.max_len - max_new)
        else:
            fit = self.max_len - max_new
        if prompt.size > fit:
            if self.chunked == "off" and prompt.size > self.buckets[-1]:
                why = (f"prompt length {prompt.size} exceeds the largest "
                       f"prefill bucket {self.buckets[-1]} (enable chunked "
                       f"prefill to lift the bucket ceiling)")
            else:
                why = (f"prompt ({prompt.size}) + max_new ({max_new}) "
                       f"exceeds the slot capacity max_len={self.max_len}")
            if self.oversize == "raise":
                raise ValueError(why)
            if self.oversize == "truncate" and fit >= 1:
                prompt = prompt[-fit:]           # keep the latest context
            else:
                rid = self._next_rid
                self._next_rid += 1
                self._results[rid] = RequestResult(
                    rid=rid, prompt_len=int(prompt.size), tokens=[],
                    finish_reason="rejected", admitted_tick=-1,
                    finished_tick=self._tick_count, error=why,
                    submit_time=now, finish_time=now)
                return rid
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid=rid, prompt=prompt, max_new=max_new,
                                   eos_id=eos_id, submit_time=now))
        return rid

    @property
    def pending(self) -> int:
        return len(self._queue) + int(self._active.sum())

    def compile_stats(self) -> Dict[str, int]:
        """Compiled-program counts — the bucket bound made observable
        (see :func:`engine.compiled_size` for the probe caveat)."""
        size = engine.compiled_size
        stats = {"prefill": size(self._prefill),
                 "tick": size(self._tick),
                 "write_slot": size(self._write)}
        if self._needs_chunk_programs:
            # ONE chunk-slab shape each, regardless of prompt lengths
            stats["chunk"] = size(self._chunk)
            stats["mixed"] = size(self._mixed)
        return stats

    def audit_programs(self) -> "collections.OrderedDict":
        """Every compiled program this scheduler dispatches, as
        ``{name: (fn, example_args)}`` with args matching the live call
        sites exactly (``jax.ShapeDtypeStruct`` stands in for the real
        operands).  Consumed by the static program auditor
        (``repro.analysis``), which traces/lowers these WITHOUT executing
        anything — keep this in sync with the ``step_tick`` / ``_admit*``
        dispatch sites above."""
        cfg = self.cfg
        i32, b1 = jnp.int32, jnp.bool_
        sds = jax.ShapeDtypeStruct

        def abstract(tree):
            return jax.tree.map(
                lambda a: sds(jnp.shape(a), jnp.result_type(a)), tree)

        params = abstract(self.params)
        pool = abstract(self._pool)
        B, V = self.max_slots, cfg.vocab_size
        logits = sds((B, V), cfg.dtype)
        active = sds((B,), b1)
        pt = ((sds((B, self.max_blocks), i32),) if self.paged else ())

        out: "collections.OrderedDict" = collections.OrderedDict()
        for b in self.buckets:
            out[f"prefill_b{b}"] = (
                self._prefill, (params, sds((1, b), i32), sds((1,), i32)))
        # the batch-1 slot cache _write scatters is prefill's second output
        # (NOT init_caches' shape: slot_prefill rewrites `length` to the
        # (1,)-shaped true_len) — eval_shape the real program
        ctx = getattr(self._prefill, "trace_context", None)
        target = getattr(self._prefill, "jitted", self._prefill)
        with (ctx() if ctx is not None else contextlib.nullcontext()):
            _, cache1 = jax.eval_shape(
                target, params, sds((1, self.buckets[0]), i32),
                sds((1,), i32))
        cache1 = abstract(cache1)
        write_args = (pool, cache1, logits, sds((1, V), cfg.dtype),
                      sds((), i32))
        if self.paged:
            write_args += (sds((self.max_blocks,), i32), sds((), i32))
        out["write"] = (self._write, write_args)
        out["tick"] = (self._tick, (params, pool, logits, active) + pt)
        if self._needs_chunk_programs:
            tokens = sds((B, self.chunk_len), i32)
            flags = (sds((B,), i32), active, active)   # valid, fresh, finish
            out["chunk"] = (self._chunk,
                            (params, pool, logits, tokens) + flags + pt)
            out["mixed"] = (self._mixed,
                            (params, pool, logits, active, tokens)
                            + flags + pt)
        if self.paged:
            out["cow"] = (self._cow, (pool, sds((), i32), sds((), i32)))
            hit_args = (pool, sds((), i32), sds((), i32))
            if self.kv_quant:
                hit_args += (sds((), i32),)        # tail_pg
            out["admit_hit"] = (self._admit_hit_plain, hit_args)
            if self._has_ssm:
                out["snap"] = (self._snap, (pool, sds((), i32)))
        return out

    def prefix_cache_stats(self) -> Dict[str, float]:
        """Prefix-cache effectiveness over everything admitted so far:
        ``hit_rate`` is the fraction of prompt tokens served straight from
        shared pages — each such token skipped its prefill compute AND its
        per-layer cache writes (``cache_write_saved_frac`` is the same
        ratio, named for what it means in paper terms: PAPER §VI counts
        avoided memory accesses; DESIGN.md §Paged KV + prefix cache)."""
        if not self.paged:
            raise ValueError("prefix_cache_stats: not a paged scheduler")
        total = max(self.prefix_stats["prompt_tokens"], 1)
        cached = self.prefix_stats["cached_tokens"]
        out = {
            "prompt_tokens": float(self.prefix_stats["prompt_tokens"]),
            "cached_tokens": float(cached),
            "prefill_tokens": float(self.prefix_stats["prefill_tokens"]),
            "hit_rate": cached / total,
            "cache_write_saved_frac": cached / total,
            "pages_in_use": float(self._pages.in_use),
            "pages_free": float(self._pages.available),
        }
        if self._radix is not None:
            out["lookups"] = float(self._radix.lookups)
            out["lookup_hits"] = float(self._radix.hits)
        return out

    def reset_prefix_stats(self) -> None:
        """Zero the prefix-cache counters (benchmarks call this after their
        warm-up traffic so the reported ratios cover only the timed trace;
        cached pages themselves stay resident)."""
        if not self.paged:
            raise ValueError("reset_prefix_stats: not a paged scheduler")
        self.prefix_stats = {k: 0 for k in self.prefix_stats}
        if self._radix is not None:
            self._radix.lookups = self._radix.hits = 0
            self._radix.tokens_hit = 0

    def step_tick(self) -> bool:
        """Admit into every free slot, feed one prompt chunk to every
        prefilling slot, run one fused multi-step decode tick for every
        decoding slot — chunk + decode in ONE jitted program when both kinds
        are live — then retire finished requests.  Returns False when there
        is nothing to do.

        Paged admission can *stall*: if the page pool cannot cover the next
        request even after evicting prefix-cache entries, the request waits
        at the queue head for in-flight slots to retire (their pages free on
        retirement); with an idle system the ``oversize`` policy applies
        instead (reject / truncate / raise) — exhaustion never crashes a
        live serve loop.
        """
        stalled = False
        for i in range(self.max_slots):
            if stalled:
                break
            while not self._active[i] and self._queue:
                req = self._queue.popleft()
                st = self._admit(i, req)
                if st == "wait":
                    self._queue.appendleft(req)
                    stalled = True
                    break
                # "ok" fills the slot (loop exits); "drop" recorded a
                # rejection — try the next queued request for this slot
        if not self._active.any():
            return False

        # ---- build this tick's chunk slab (chunked admissions only) -------
        chunk_rows = [i for i, s in enumerate(self._slots)
                      if s is not None and s.phase == "prefill"]
        valid = np.zeros((self.max_slots,), np.int32)
        defer = np.zeros((self.max_slots,), bool)
        if chunk_rows:
            tokens = np.zeros((self.max_slots, self.chunk_len), np.int32)
            fresh = np.zeros((self.max_slots,), bool)
            finishing = np.zeros((self.max_slots,), bool)
            for i in chunk_rows:
                s = self._slots[i]
                take = min(self.chunk_len,
                           s.req.prompt.size - s.prefill_pos)
                tokens[i, :take] = s.req.prompt[s.prefill_pos:
                                                s.prefill_pos + take]
                valid[i] = take
                fresh[i] = s.prefill_pos == 0 and s.hit_len == 0
                finishing[i] = s.prefill_pos + take >= s.req.prompt.size
                # hybrid-model snapshot capture needs the post-prompt SSM
                # state BEFORE any decode step touches it: when the final
                # chunk lands exactly on the cacheable (page-aligned) prompt
                # boundary, hold the row out of this tick's decode scan and
                # capture after the tick — it starts decoding next tick with
                # identical tokens (the logits/state don't change)
                defer[i] = finishing[i] and (
                    self._defer_decode
                    or (self._wants_snapshot(s)
                        and s.prefill_pos + take
                        == self._cacheable_len(s.req.prompt.size)))
        # a slot whose LAST chunk lands this tick decodes in the same tick:
        # the chunk phase writes its first-token logits before the scan runs
        decode_mask = np.array(
            [s is not None and not s.done
             and (s.phase == "decode"
                  or (chunk_rows and finishing[i] and not defer[i]))
             for i, s in enumerate(self._slots)])

        if self.first_logits is not None:
            for i in np.flatnonzero(decode_mask):
                s = self._slots[i]
                if s.phase == "decode" and not s.tokens:
                    self.first_logits[s.req.rid] = np.asarray(self._logits[i])

        pt = (jnp.asarray(self._table),) if self.paged else ()
        toks_h = fracs_h = cfrac_h = None
        if chunk_rows and decode_mask.any():
            lg, pool, toks, fracs, cfrac = self._mixed(
                self.params, self._pool, self._logits,
                jnp.asarray(decode_mask), jnp.asarray(tokens),
                jnp.asarray(valid), jnp.asarray(fresh),
                jnp.asarray(finishing), *pt)
            self._logits, self._pool = lg, pool
            toks_h, fracs_h = np.asarray(toks), np.asarray(fracs)
            cfrac_h = np.asarray(cfrac)
        elif chunk_rows:
            lg, pool, cfrac = self._chunk(
                self.params, self._pool, self._logits, jnp.asarray(tokens),
                jnp.asarray(valid), jnp.asarray(fresh),
                jnp.asarray(finishing), *pt)
            self._logits, self._pool = lg, pool
            cfrac_h = np.asarray(cfrac)
        else:
            lg, pool, toks, fracs = self._tick(
                self.params, self._pool, self._logits,
                jnp.asarray(decode_mask), *pt)
            self._logits, self._pool = lg, pool
            toks_h, fracs_h = np.asarray(toks), np.asarray(fracs)

        now = time.perf_counter()

        # ---- chunk-phase bookkeeping --------------------------------------
        for i in chunk_rows:
            s = self._slots[i]
            s.prefill_pos += int(valid[i])
            if finishing[i]:
                s.phase = "decode"
            if (self._wants_snapshot(s) and s.prefill_pos
                    == self._cacheable_len(s.req.prompt.size)):
                # post-tick state is exactly the state at prefill_pos: the
                # row was held out of (or not yet in) the decode scan, and
                # inactive rows' recurrent state is masked frozen
                s.snapshot = self._snap(self._pool,
                                        jnp.asarray(i, jnp.int32))
            if self.with_stats and cfrac_h is not None:
                # the chunk forward's batch-aggregate traffic, attributed to
                # the requests that prefilled this tick (decode steps are
                # attributed below, exactly as before)
                s.frac_sums[0] += float(cfrac_h[0])
                s.frac_sums[1] += float(cfrac_h[1])
                s.frac_steps += 1

        # ---- decode-phase bookkeeping -------------------------------------
        if toks_h is not None:
            for t in range(self.tick_steps):
                for i, slot in enumerate(self._slots):
                    if slot is None or slot.done or not decode_mask[i]:
                        continue
                    tok = int(toks_h[i, t])
                    if not slot.tokens:
                        slot.first_token_time = now
                    slot.tokens.append(tok)
                    if self.with_stats:
                        slot.frac_sums[0] += float(fracs_h[t, 0])
                        slot.frac_sums[1] += float(fracs_h[t, 1])
                        slot.frac_steps += 1
                    if slot.req.eos_id is not None \
                            and tok == slot.req.eos_id:
                        slot.done, slot.finish_reason = True, "eos"
                    elif len(slot.tokens) >= slot.req.max_new:
                        slot.done, slot.finish_reason = True, "length"

        self._tick_count += 1
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.done:
                self._retire(i)
        return True

    def run(self, max_ticks: Optional[int] = None) -> List[RequestResult]:
        """Drive ticks until queue and slots drain (or ``max_ticks``);
        returns every finished result in rid order."""
        ticks = 0
        while self.pending and (max_ticks is None or ticks < max_ticks):
            if not self.step_tick():
                break
            ticks += 1
        return [self._results[rid] for rid in sorted(self._results)]

    # ------------------------------------------------------------ internals

    def _uses_chunks(self, prompt_len: int) -> bool:
        """Chunk-vs-bucket admission policy: ``"always"`` chunks everything;
        ``"auto"`` chunks only prompts no bucket can hold, so in-bucket
        prompts keep the bucketed path's bit-exact token guarantee."""
        if self.chunked == "always":
            return True
        return self.chunked == "auto" and prompt_len > self.buckets[-1]

    def _wants_snapshot(self, slot: _Slot) -> bool:
        """Hybrid/SSM models need the recurrent state at the cacheable
        prompt boundary for a prefix hit to be usable; capture it once,
        opportunistically, when ingestion lands exactly on that boundary."""
        return (self._radix is not None and self._has_ssm
                and slot.snapshot is None)

    def _cacheable_len(self, prompt_len: int) -> int:
        """Prompt tokens coverable by whole shared pages."""
        return (prompt_len // self.page_len) * self.page_len

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` fresh pages, evicting LRU prefix-cache entries
        if the free list runs short.  All-or-nothing — and eviction only
        runs when it can actually satisfy the request: an unsatisfiable
        allocation (oversized request, under-provisioned pool) must not
        drain the whole prefix cache on its way to being rejected."""
        got = self._pages.alloc(n)
        if (got is None and self._radix is not None
                and self._pages.available + self._radix.evictable_pages()
                >= n):
            self._radix.evict(n)
            got = self._pages.alloc(n)
        return got

    def _admit(self, slot_idx: int, req: Request) -> str:
        """Fill ``slot_idx`` with ``req``; returns ``"ok"`` (admitted),
        ``"wait"`` (paged pool exhausted while other requests are in
        flight — retry next tick), or ``"drop"`` (request rejected with a
        per-request error result)."""
        if self.paged:
            return self._admit_paged(slot_idx, req)
        length = int(req.prompt.size)
        if self._uses_chunks(length):
            # chunked ingestion: no prefill here — step_tick feeds the
            # prompt chunk-by-chunk into the pool, interleaved with decode
            self._active[slot_idx] = True
            self._slots[slot_idx] = _Slot(req=req,
                                          admitted_tick=self._tick_count,
                                          phase="prefill")
            return "ok"
        self._admit_bucketed(slot_idx, req)
        return "ok"

    def _admit_bucketed(self, slot_idx: int, req: Request,
                        page_args: tuple = ()) -> None:
        """Monolithic bucketed prefill + slot write (dense or paged)."""
        length = int(req.prompt.size)
        bucket = bucket_for(length, self.buckets)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :length] = req.prompt
        logits1, cache1 = self._prefill(self.params, jnp.asarray(padded),
                                        jnp.asarray([length], jnp.int32))
        self._pool, self._logits = self._write(
            self._pool, cache1, self._logits, logits1,
            jnp.asarray(slot_idx, jnp.int32), *page_args)
        self._active[slot_idx] = True
        self._slots[slot_idx] = _Slot(req=req,
                                      admitted_tick=self._tick_count)

    def _admit_paged(self, slot_idx: int, req: Request,
                     retrying: bool = False) -> str:
        prompt = req.prompt
        length = int(prompt.size)
        pl = self.page_len
        hit = None
        if self._radix is not None:
            # cap the hit at length-1: at least one suffix token must run
            # through prefill to produce the first decode logits
            hit = self._radix.lookup(prompt, max_hit=length - 1,
                                     need_snapshot=self._has_ssm,
                                     min_hit=self.min_prefix_hit,
                                     allow_partial=not self._has_ssm)
        shared = list(hit.pages) if hit is not None else []
        # hold references on every page the hit aliases (shared blocks AND
        # the COW source) BEFORE allocating: allocation may evict radix
        # entries, and the tree's reference may be the only thing keeping
        # these pages alive — without the hold, eviction could free one
        # and the allocator hand it back to us as a "fresh" page
        hold = shared + ([hit.cow_src] if hit is not None
                         and hit.cow_src is not None else [])
        self._pages.ref(hold)
        # worst-case tokens the slot writes: prompt + generation + the junk
        # tail of the tick in which it finishes (same clamp bound as dense)
        need_tokens = min(self.max_len,
                          length + req.max_new + self.tick_steps)
        n_blocks = blocks_for_tokens(need_tokens, pl)
        fresh = self._alloc_pages(n_blocks - len(shared))
        if fresh is None:
            self._pages.release(hold)    # the pool is untouched again
            if self._active.any():
                return "wait"
            why = (f"page pool exhausted: request needs {n_blocks} pages "
                   f"({need_tokens} tokens @ page_len={pl}), "
                   f"{self._pages.available} free of "
                   f"{self._pages.capacity}")
            if self.oversize == "raise":
                raise ValueError(why)
            if self.oversize == "truncate" and not retrying:
                # truncate to what the pool could hold after evicting the
                # prefix cache (the retry's allocation performs the actual
                # eviction), capped at the slot capacity like dense
                usable = self._pages.available + (
                    self._radix.evictable_pages()
                    if self._radix is not None else 0)
                fit = min(usable * pl - req.max_new - self.tick_steps,
                          self.max_len - req.max_new)
                if fit >= 1:
                    cut = dataclasses.replace(req, prompt=prompt[-fit:])
                    return self._admit_paged(slot_idx, cut, retrying=True)
            now = time.perf_counter()
            self._results[req.rid] = RequestResult(
                rid=req.rid, prompt_len=length, tokens=[],
                finish_reason="rejected", admitted_tick=-1,
                finished_tick=self._tick_count, error=why,
                submit_time=req.submit_time, finish_time=now)
            return "drop"
        if hit is not None and hit.cow_src is not None:
            # the partially-matching page is copied into the first fresh
            # page (it IS block len(shared)); the slot owns the copy
            # exclusively, so suffix ingestion can overwrite its tail.
            # The hold reference on the source is dropped after the copy.
            self._pool = self._cow(self._pool,
                                   jnp.asarray(hit.cow_src, jnp.int32),
                                   jnp.asarray(fresh[0], jnp.int32))
            self._pages.release([hit.cow_src])
        pages = shared + fresh
        self._table[slot_idx, :] = TRASH_PAGE
        self._table[slot_idx, :len(pages)] = pages
        self.prefix_stats["prompt_tokens"] += length
        if hit is not None:
            # restore length (and SSM state, hybrid models) at the hit
            # boundary, then ingest only the suffix through the chunk path
            idx = jnp.asarray(slot_idx, jnp.int32)
            hl = jnp.asarray(hit.length, jnp.int32)
            # quantized pool: the slot's tail ring must be seeded from the
            # hit's newest page (the previous occupant's ring rows are
            # junk); the table row above already names that page
            tpg = ((jnp.asarray(
                int(self._table[slot_idx, (hit.length - 1) // pl]),
                jnp.int32),) if self.kv_quant else ())
            if hit.snapshot is not None:
                self._pool = self._admit_hit_snap(self._pool, idx, hl,
                                                  hit.snapshot, *tpg)
            else:
                self._pool = self._admit_hit_plain(self._pool, idx, hl,
                                                   *tpg)
            slot = _Slot(req=req, admitted_tick=self._tick_count,
                         phase="prefill", prefill_pos=hit.length,
                         hit_len=hit.length)
            self.prefix_stats["cached_tokens"] += hit.length
            self.prefix_stats["prefill_tokens"] += length - hit.length
        elif self._uses_chunks(length):
            slot = _Slot(req=req, admitted_tick=self._tick_count,
                         phase="prefill")
            self.prefix_stats["prefill_tokens"] += length
        else:
            self._admit_bucketed(
                slot_idx, req,
                page_args=(jnp.asarray(self._table[slot_idx]),
                           jnp.asarray(length, jnp.int32)))
            slot = self._slots[slot_idx]
            self.prefix_stats["prefill_tokens"] += length
            if (self._wants_snapshot(slot) and length % pl == 0):
                # page-aligned prompt: the freshly-written slot state IS
                # the state at the cacheable boundary — snapshot now,
                # before any decode tick advances it
                slot.snapshot = self._snap(self._pool,
                                           jnp.asarray(slot_idx, jnp.int32))
        slot.pages = pages
        self.prefix_stats["pages_held"] += len(pages)
        self.prefix_stats["admitted"] += 1
        self._active[slot_idx] = True
        self._slots[slot_idx] = slot
        return "ok"

    def _free_slot(self, slot_idx: int) -> None:
        """Release ``slot_idx`` WITHOUT recording a result: donate the
        prompt's pages to the prefix cache, drop the slot's page
        references, clear the table row and the active bit.  ``_retire``
        (result-recording retirement) and the prefill engine's
        export-then-release path (``serving/workers.py`` — the span, not
        a result, is the output) share this."""
        slot = self._slots[slot_idx]
        if self.paged:
            if self._radix is not None:
                # donate the prompt's whole-page blocks to the prefix cache
                # (existing nodes are re-used, new nodes take their own page
                # refs) BEFORE releasing the slot's references
                row = self._table[slot_idx]
                self._radix.insert(slot.req.prompt,
                                   lambda bi: int(row[bi]),
                                   snapshot=slot.snapshot)
            self._pages.release(slot.pages)
            self._table[slot_idx, :] = TRASH_PAGE
        self._active[slot_idx] = False
        self._slots[slot_idx] = None

    def _retire(self, slot_idx: int) -> None:
        slot = self._slots[slot_idx]
        self._free_slot(slot_idx)
        n = max(slot.frac_steps, 1)
        self._results[slot.req.rid] = RequestResult(
            rid=slot.req.rid,
            prompt_len=int(slot.req.prompt.size),
            tokens=list(slot.tokens),
            finish_reason=slot.finish_reason,
            admitted_tick=slot.admitted_tick,
            finished_tick=self._tick_count,
            plane_traffic_fraction=(slot.frac_sums[0] / n
                                    if self.with_stats else float("nan")),
            element_traffic_fraction=(slot.frac_sums[1] / n
                                      if self.with_stats else float("nan")),
            submit_time=slot.req.submit_time,
            first_token_time=slot.first_token_time,
            finish_time=time.perf_counter(),
        )
