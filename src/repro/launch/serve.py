"""Serving driver: prefill a batch of prompts, decode new tokens, report
tokens/s.  Mesh-aware (TP sharding of params and caches); the decode phase is
the FUSED ``lax.scan`` loop — one XLA program for all new tokens, no
per-token dispatch.  CPU smoke:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16

``--continuous`` switches to the continuous-batching slot scheduler
(``serving/scheduler.py``): a queued trace of variable-length prompts is
admitted into a persistent slot pool, stepped in fused multi-token ticks,
and retired/re-filled on EOS or length — decode never drains:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --continuous --requests 16 --max-slots 4 --new-tokens 16 --quant

``--mesh DxM`` (e.g. ``2x2``, ``4x1``) runs either mode tensor/data-parallel
over a ``data x model`` host mesh: params get the TP rules (incl. packed bit
-planes), the slot pool shards batch-on-data, and the token stream is
bit-equal to the single-device run (tests/test_serve_sharded.py).  On a CPU
box add ``--host-devices N`` (must be the FIRST jax knob to take effect — it
sets ``XLA_FLAGS=--xla_force_host_platform_device_count`` before jax init):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --continuous --mesh 2x2 --host-devices 4
"""

from __future__ import annotations

import argparse
import math
import sys
import time

# must precede the first jax import: jax locks the device count at init
# (repro.launch.host_devices is deliberately jax-free)
if __name__ == "__main__":
    from repro.launch.host_devices import force_host_devices
    force_host_devices(sys.argv)

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke
from repro.launch.mesh import batch_axes, make_serve_mesh
from repro.launch.runtime import enable_compile_cache
from repro.launch.shardings import (cache_shardings, init_params_sharded,
                                    params_shardings)
from repro.models.model import init_caches
from repro.models.quantize import quantize_model_params
from repro.models.sharding import mesh_axes
from repro.serving.engine import make_decode_loop, make_prefill_step


def build_serve_config(args):
    """Pure flags -> :class:`~repro.serving.config.ServeConfig` mapping
    for ``--continuous`` serving.  No jax state is touched: the same
    flags always produce the same config, and ``--dump-config`` commits
    exactly what this returns (round-trip tested).  The mesh is the one
    deliberate exclusion — device binding is process-local, so the
    launcher resolves ``--mesh`` itself and passes the live mesh
    alongside the config (``ServeConfig.mesh_spec`` stays for configs
    authored by hand)."""
    from repro.serving.config import ServeConfig
    from repro.serving.scheduler import round_pool_len

    buckets = tuple(sorted({8, 16, max(8, args.prompt_len)}))
    chunked = args.chunked or "off"
    chunk_len = args.chunk_len or 8
    long_max = (3 * args.prompt_len) if chunked != "off" else args.prompt_len
    pool = max(long_max, max(buckets)) + args.new_tokens + args.tick_steps
    # ONE rounding to the lcm: sequential round-ups could undo each other
    # (e.g. chunk 12 then page 16 yields 112, not a multiple of 12)
    quantum = 1
    if chunked != "off" or args.prefix_cache:
        quantum = chunk_len
    kv_quant = args.kv_quant is not None
    paged = bool(args.paged or args.prefix_cache or args.attn_kernel
                 or kv_quant)
    if paged:
        quantum = math.lcm(quantum, args.page_len)
    if quantum > 1:
        pool = round_pool_len(pool, quantum)
    return ServeConfig(
        max_slots=args.max_slots, max_len=pool, buckets=buckets,
        quant=args.quant_backend if args.quant else False,
        with_stats=args.quant, tick_steps=args.tick_steps,
        chunked=chunked, chunk_len=chunk_len, paged=paged,
        page_len=args.page_len, prefix_cache=args.prefix_cache,
        attn_kernel="pallas" if args.attn_kernel else "off",
        attn_splits=args.attn_splits,
        kv_quant=kv_quant, kv_bits=args.kv_quant or 4)


def _load_serve_config(args):
    """The serving config for this invocation: ``--config path.json`` if
    given (the committed-file workflow), else derived from the flags."""
    from repro.serving.config import ServeConfig

    if args.config is None:
        return build_serve_config(args)
    with open(args.config) as fh:
        return ServeConfig.from_json(fh.read())


def continuous_trace(cfg, config, *, requests: int, prompt_len: int,
                     seed: int):
    """The seeded request trace ``--continuous`` serves: ``requests``
    prompts of 2..``prompt_len`` tokens (up to 3x ``prompt_len`` with
    chunked prefill — past every bucket), and with a prefix cache a
    shared-system-prompt workload (half the prompt is a common prefix) so
    the radix tree has something to hit."""
    import numpy as np

    long_max = (3 * prompt_len) if config.chunked != "off" else prompt_len
    rng = np.random.default_rng(seed)
    prefix = (rng.integers(0, cfg.vocab_size, size=max(prompt_len // 2,
                                                       config.page_len))
              .astype(np.int32) if config.prefix_cache else None)
    prompts = []
    for _ in range(requests):
        n = int(rng.integers(2, long_max + 1))
        p = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        if prefix is not None and rng.random() < 0.75:
            p = np.concatenate([prefix, p])[:max(long_max, len(prefix) + 2)]
        prompts.append(p)
    return prompts


def serve_continuous(cfg, params, config, prompts, *, max_new: int,
                     eos_id=None, mesh=None, disaggregate: bool = False,
                     keep_first_logits: bool = False):
    """Serve ``prompts`` through the continuous-batching scheduler (or,
    with ``disaggregate``, the prefill/decode router — identical tokens,
    isolated decode ticks): submit everything, drain.  Returns
    ``(results, scheduler, seconds)``; with ``keep_first_logits`` the
    scheduler's ``first_logits`` holds each bucket-admitted request's
    first-token logits."""
    from repro.serving.router import Router
    from repro.serving.scheduler import ServeScheduler

    if disaggregate:
        if not config.paged:
            raise SystemExit("--disaggregate requires a paged config "
                             "(add --paged, or paged=true in --config)")
        sched = Router(cfg, params, config, mesh=mesh)
    else:
        sched = ServeScheduler(cfg, params, config, mesh=mesh)
        if keep_first_logits:
            sched.first_logits = {}
    for p in prompts:
        sched.submit(p, max_new=max_new, eos_id=eos_id)
    t0 = time.perf_counter()
    results = sched.run()
    return results, sched, time.perf_counter() - t0


def _serve_continuous(cfg, params, args, mesh):
    """Queued-trace continuous batching (:func:`serve_continuous` over
    :func:`continuous_trace`), reporting sustained tok/s + per-request
    latency + plane traffic.

    With ``--chunked`` the trace includes LONG prompts (up to 3x
    ``--prompt-len``, past every prefill bucket) — rejected outright without
    chunking — ingested ``--chunk-len`` tokens per tick, interleaved with
    decode.  ``--disaggregate`` serves the same trace through the
    prefill/decode router (``serving/router.py``) instead of the combined
    scheduler."""
    import numpy as np

    config = _load_serve_config(args)
    buckets = config.buckets
    chunked = config.chunked
    live_mesh = mesh if mesh is not None and mesh.size > 1 else None
    prompts = continuous_trace(cfg, config, requests=args.requests,
                               prompt_len=args.prompt_len, seed=args.seed)
    results, sched, dt = serve_continuous(
        cfg, params, config, prompts, max_new=args.new_tokens,
        eos_id=args.eos_id, mesh=live_mesh, disaggregate=args.disaggregate)
    total = sum(len(r.tokens) for r in results)
    mesh_tag = ("1-device" if live_mesh is None else
                "x".join(str(s) for s in live_mesh.devices.shape) + " mesh")
    chunk_tag = ("" if chunked == "off"
                 else f", chunked={chunked}/{config.chunk_len}")
    if config.paged:
        chunk_tag += (f", paged/{config.page_len}"
                      + ("+prefix" if config.prefix_cache else "")
                      + (f"+kernel/s{config.attn_splits}"
                         if config.attn_kernel != "off" else "")
                      + (f"+kvq/{config.kv_bits}b" if config.kv_quant
                         else ""))
    if args.disaggregate:
        mode_tag = "disaggregated"
        compile_stats = {"prefill": sched.prefill.scheduler.compile_stats(),
                         "decode": sched.decode.scheduler.compile_stats()}
        stats_sched = sched.prefill.scheduler
    else:
        mode_tag = "continuous batching"
        compile_stats = sched.compile_stats()
        stats_sched = sched
    print(f"[serve] {cfg.name}: {mode_tag} ({mesh_tag}{chunk_tag}) "
          f"— {len(results)} requests, {config.max_slots} slots, "
          f"tick={config.tick_steps}: "
          f"{total} tokens in {dt:.3f}s ({total / max(dt, 1e-9):.1f} tok/s "
          f"incl. compile); programs: {compile_stats}")
    if args.disaggregate and sched.decode_tick_times:
        tt = np.asarray(sched.decode_tick_times) * 1e3
        print(f"[serve] decode fleet: {len(tt)} isolated ticks, p50/p95 "
              f"{np.percentile(tt, 50):.1f}/{np.percentile(tt, 95):.1f} ms "
              f"(prefill work excluded by construction)")
    if not results:
        return
    served = [r for r in results if r.finish_reason != "rejected"]
    ttft = [r.first_token_time - r.submit_time for r in served
            if np.isfinite(r.first_token_time)]
    e2e = [r.finish_time - r.submit_time for r in served
           if np.isfinite(r.finish_time)]
    if ttft:
        print(f"[serve] latency (incl. compile): ttft p50/p95 "
              f"{np.percentile(ttft, 50) * 1e3:.1f}/"
              f"{np.percentile(ttft, 95) * 1e3:.1f} ms, e2e p50/p95 "
              f"{np.percentile(e2e, 50) * 1e3:.1f}/"
              f"{np.percentile(e2e, 95) * 1e3:.1f} ms; "
              f"{len(served)}/{len(results)} served, longest prompt "
              f"{max(r.prompt_len for r in served)} tokens "
              f"(buckets cap {max(buckets)})")
    if args.quant:
        tile = float(np.mean([r.plane_traffic_fraction for r in served]))
        elem = float(np.mean([r.element_traffic_fraction for r in served]))
        print(f"[serve] per-request plane_traffic_fraction: {tile:.3f} "
              f"tile-granular, {elem:.3f} element-granular")
    if config.prefix_cache:
        st = stats_sched.prefix_cache_stats()
        print(f"[serve] prefix cache: hit_rate {st['hit_rate']:.3f} "
              f"({int(st['cached_tokens'])}/{int(st['prompt_tokens'])} "
              f"prompt tokens from shared pages, "
              f"{int(st['lookup_hits'])}/{int(st['lookups'])} lookups hit; "
              f"pages {int(st['pages_in_use'])} in use / "
              f"{int(st['pages_free'])} free)")
    r0 = results[0]
    print(f"sample request 0 ({r0.finish_reason}):", r0.tokens[:8])


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's flags (``main`` parses these; ``chip_smoke.py``
    derives its serving configs from the same flags)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="host",
                    help="'host', 'pod', 'pod2', or an explicit DxM "
                         "data x model grid (e.g. '2x2', '4x1')")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force N host (CPU) devices for a local mesh smoke "
                         "run (consumed before jax init; see module "
                         "docstring)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--quant", action="store_true")
    ap.add_argument("--quant-backend", default="pallas",
                    choices=["pallas", "xla"])
    ap.add_argument("--pack", action="store_true",
                    help="serve packed bit-planes (int8-footprint deploy "
                         "format)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="enable while_loop early stop on this token id")
    ap.add_argument("--seed", type=int, default=0)
    # continuous-batching mode
    ap.add_argument("--continuous", action="store_true",
                    help="serve a queued request trace through the slot "
                         "scheduler instead of one rectangular batch")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--tick-steps", type=int, default=8)
    ap.add_argument("--chunked", nargs="?", const="auto", default=None,
                    choices=["off", "auto", "always"],
                    help="chunked prefill (continuous mode): ingest prompts "
                         "chunk-by-chunk interleaved with decode; lifts the "
                         "bucket ceiling on prompt length, and the trace "
                         "draws prompts up to 3x --prompt-len.  Bare "
                         "--chunked means 'auto' (only over-bucket prompts "
                         "chunk); 'always' chunks every prompt")
    ap.add_argument("--chunk-len", type=int, default=None,
                    help="tokens ingested per chunk per tick (default 8, "
                         "the smallest bucket)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool (continuous mode): slots share a "
                         "pool of fixed-size pages through per-slot page "
                         "tables instead of owning dense cache slabs")
    ap.add_argument("--page-len", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--attn-kernel", action="store_true",
                    help="fused paged-attention decode kernel (implies "
                         "--paged): walks the page tables directly instead "
                         "of gathering pool[table] into the dense view "
                         "(DESIGN.md §Paged attention kernel)")
    ap.add_argument("--attn-splits", type=int, default=1,
                    help="split-KV flash-decode: partition the KV page axis "
                         "into this many independent softmax partials, "
                         "merged at the end (rides the model mesh axis "
                         "when it divides)")
    ap.add_argument("--kv-quant", nargs="?", const=4, type=int,
                    default=None, metavar="BITS",
                    help="log2-quantize completed KV pages at BITS wire "
                         "exponent bits (default 4; implies --paged — "
                         "newest pages stay f32 in the per-slot tail ring)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged pool (implies "
                         "--paged): requests re-use the cached KV of their "
                         "longest shared prompt prefix and prefill only "
                         "the suffix; the trace draws shared-prefix "
                         "prompts to show hits")
    ap.add_argument("--config", default=None, metavar="PATH",
                    help="load the continuous-mode ServeConfig from this "
                         "JSON file instead of deriving it from the flags "
                         "(--dump-config writes the derived form)")
    ap.add_argument("--dump-config", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="print (or write to PATH) the ServeConfig JSON "
                         "this flag combination derives, then exit — the "
                         "committed-config workflow's authoring step")
    ap.add_argument("--disaggregate", action="store_true",
                    help="continuous mode through the disaggregated "
                         "prefill/decode router (serving/router.py) "
                         "instead of the combined scheduler: identical "
                         "tokens, decode ticks isolated from prompt "
                         "ingestion (requires a paged config)")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    if args.dump_config is not None:
        text = _load_serve_config(args).to_json(indent=2)
        if args.dump_config == "-":
            print(text)
        else:
            with open(args.dump_config, "w") as fh:
                fh.write(text + "\n")
        return

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend == "audio_stub":
        raise SystemExit("use examples/serve_decode.py for the audio stub")
    mesh = make_serve_mesh(args.mesh, args.model_parallel)
    bax = batch_axes(mesh)
    max_len = args.prompt_len + args.new_tokens

    quant = args.quant_backend if args.quant else False
    with mesh, mesh_axes(batch=bax, model="model", seq_shard=False,
                         sizes=dict(mesh.shape), mesh=mesh):
        params = init_params_sharded(jax.random.PRNGKey(args.seed), cfg,
                                     mesh)
        if args.quant:
            params = quantize_model_params(cfg, params, pack=args.pack)
            params = jax.device_put(
                params, params_shardings(mesh, params, fsdp=False))
        if args.continuous:
            return _serve_continuous(cfg, params, args, mesh)
        caches = init_caches(cfg, args.batch, max_len, dtype=cfg.dtype)
        # ssm_model=False: this path EXECUTES decode — a model-sharded SSM
        # recurrent carry is the documented CPU-SPMD miscompile (DESIGN.md
        # §Sharded serving); only lowering-only consumers keep it
        csh = cache_shardings(mesh, caches, batch=args.batch,
                              ssm_model=False)
        caches = jax.device_put(caches, csh)

        key = jax.random.PRNGKey(args.seed)
        prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
        if cfg.frontend == "vision_stub":
            n_img = cfg.n_image_tokens
            img = jax.random.normal(key, (args.batch, n_img, cfg.d_model),
                                    jnp.bfloat16)
            batch = {"tokens": prompt, "image_embeds": img}
        else:
            batch = {"tokens": prompt}

        prefill = jax.jit(make_prefill_step(cfg, quant),
                          donate_argnums=(2,))
        decode = jax.jit(make_decode_loop(cfg, args.new_tokens, quant=quant,
                                          eos_id=args.eos_id,
                                          with_stats=args.quant),
                         donate_argnums=(1,))

        t0 = time.perf_counter()
        logits, caches = prefill(params, batch, caches)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        t1 = time.perf_counter()
        toks, stats = decode(params, caches, logits, key)
        jax.block_until_ready(toks)
        t_decode = time.perf_counter() - t1

    import numpy as np
    toks_h = np.asarray(toks)
    if args.eos_id is None:
        total_new = toks_h.size
        steps = args.new_tokens
    else:
        # early stop: count per-row tokens up to (and including) the first
        # EOS, and only the while_loop iterations that actually executed —
        # trailing slots are EOS padding / zeroed stats
        hits = toks_h == args.eos_id
        first = np.where(hits.any(1), hits.argmax(1) + 1, args.new_tokens)
        total_new = int(first.sum())
        steps = int(first.max()) if args.new_tokens else 0
    print(f"[serve] {cfg.name}: prefill {args.batch}x{args.prompt_len} "
          f"in {t_prefill:.3f}s; {total_new} tokens decoded in "
          f"{t_decode:.3f}s ({total_new / max(t_decode, 1e-9):.1f} tok/s, "
          f"fused scan incl. compile)")
    if stats is not None and steps:
        # average over executed forwards only: the terminal while_loop
        # iteration no longer steps the model (its logits were dead) and
        # reports exact-zero traffic for that slot
        tile_all = np.asarray(stats["plane_traffic_fraction"][:steps])
        ran = tile_all > 0
        tile = float(tile_all[ran].mean()) if ran.any() else 0.0
        elem_all = np.asarray(stats["element_traffic_fraction"][:steps])
        elem = float(elem_all[ran].mean()) if ran.any() else 0.0
        print(f"[serve] plane_traffic_fraction: {tile:.3f} tile-granular "
              f"(kernel DMA), {elem:.3f} element-granular (ASIC model)")
    print("sample tokens:", toks_h[0, :8].tolist())


if __name__ == "__main__":
    main()
