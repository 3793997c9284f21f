#!/usr/bin/env python3
"""On-chip smoke test: the serving path at a published width, on a TPU.

    python chip_smoke.py              # one chip: phases a-c below
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

One process drives the chip(s) from start to end.  Phases (one chip):

a. Device check: JAX's first device must be a TPU.  Checked before any
   kernel is built, so an interpret-mode fallback can never stand in for
   Mosaic.
b. Kernels at real widths, each against its reference on the chip: paged
   attention (f32/bf16, splits 1 and 2, q4/q8 pools, page_len 16) within
   stated tolerances, the bit-plane GEMM (K=576, N=1536) and log2quant on
   decode activations bit for bit.  Every compiled kernel program must
   contain ``tpu_custom_call`` (the kernel ran in Mosaic, not interpreted).
c. Serving smollm-135m at its published width through the same function
   the ``--continuous`` CLI runs (``repro.launch.serve.serve_continuous``),
   paged + chunked + Pallas attention, float then quantized: every request
   finishes with in-vocab tokens, first-token logits match the dense
   prefill path, and the quantized ``pallas`` and ``xla`` backends emit
   equal tokens.

``--chips 4`` serves qwen2.5-14b at its published width on a 1x4 (data x
model) mesh and checks a 4-layer cut of it, float and quantized, against
the same model on one of the four chips.

Any failed check exits non-zero before the result line.  The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
Times printed on the way include compilation and are set-up, not
measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# bf16 serving logits vs the dense read path, relative to max |logit|: ten
# bf16 roundings (2^-8 each), for two programs that order a 30-layer bf16
# forward differently
LOGIT_RTOL = 0.04
# paged attention kernel vs the dense-gather reference (outputs are convex
# combinations of unit-normal values, so these are absolute errors on O(1))
ATTN_ATOL = {"float32": 1e-4, "bfloat16": 5e-2}


FAILURES: list = []


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    """Record a failed check and go on, so that one run reports every
    check; ``main`` exits non-zero, without the result line, if any
    failed."""
    if not cond:
        FAILURES.append(msg)
        log(f"FAILED: {msg}")


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------

def device_info(want: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU: JAX's first device is {d0.platform!r} ({d0.device_kind})")
    check(len(devs) >= want, f"need {want} chips, JAX sees {len(devs)}")
    log(f"device: {d0.device_kind} x{len(devs)} ({jax.__version__})")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def compiled_text(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# b. kernels
# ---------------------------------------------------------------------------

def _table(lengths, nb, page_len):
    import numpy as np

    table = np.zeros((len(lengths), nb), np.int32)
    nxt = 1
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // page_len)):
            table[i, j] = nxt
            nxt += 1
    return table


def phase_kernels() -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.bitplane import to_bitplanes
    from repro.core.logquant import (code_dtype, dequantize_page_codes,
                                     log2_quantize, quantize_page_codes,
                                     scale_exponent)
    from repro.core.shiftadd import shiftadd_matmul_bitplane
    from repro.kernels.bitplane_matmul.ops import bitplane_matmul_pallas
    from repro.kernels.log2quant.ops import log2_quantize_pallas
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_decode_attention_quant)
    from repro.kernels.paged_attention.ref import paged_attention_reference

    cfg = get_config("smollm-135m")
    h, g, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    page_len, nb = 16, 16
    lengths = np.array([1, 17, 100, 256, 33, 64, 200, 5], np.int32)
    b = len(lengths)
    n_pages = 1 + b * nb
    table = jnp.asarray(_table(lengths, nb, page_len))
    lens = jnp.asarray(lengths)
    rng = np.random.default_rng(0)
    q32 = rng.standard_normal((b, 1, h, d)).astype(np.float32)
    k32 = rng.standard_normal((n_pages, page_len, g, d)).astype(np.float32)
    v32 = rng.standard_normal((n_pages, page_len, g, d)).astype(np.float32)

    def ref_attn(q, k, v, tab=table, ln=lens):
        # the TPU's default f32 matmul takes one bf16 pass; the context is
        # live through tracing, lowering and compiling of the jit
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(paged_attention_reference)(
                q, k, v, tab, ln), np.float32)

    def ref_f64(q, k, v):
        """float64 on the host, for the log: how far the on-chip reference
        itself is from exact."""
        q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
        tab = np.asarray(table)
        kg = k[tab].reshape(b, nb * page_len, g, d)
        vg = v[tab].reshape(b, nb * page_len, g, d)
        qg = q.reshape(b, g, h // g, d)
        s = np.einsum("bgrd,bkgd->bgrk", qg, kg) / np.sqrt(d)
        s = np.where(np.arange(nb * page_len) < lengths[:, None, None, None],
                     s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bgrk,bkgd->bgrd", p, vg).reshape(b, 1, h, d)

    def compare(what, out, ref, exact, tol):
        out = np.asarray(out, np.float32)
        err = float(np.max(np.abs(out - ref)))
        log(f"{what}: max|kernel - ref| = {err:.3e} (tol {tol:g}); vs "
            f"float64: kernel {np.max(np.abs(out - exact)):.3e}, ref "
            f"{np.max(np.abs(ref - exact)):.3e}")
        check(np.isfinite(out).all() and err <= tol, f"{what}: err {err}")

    for dt in (jnp.float32, jnp.bfloat16):
        q, k, v = (jnp.asarray(x, dt) for x in (q32, k32, v32))
        ref, exact = ref_attn(q, k, v), ref_f64(q, k, v)
        for splits in (1, 2):
            fn = functools.partial(paged_decode_attention, splits=splits,
                                   interpret=False)
            what = f"paged attention {jnp.dtype(dt).name} splits={splits}"
            check("tpu_custom_call" in compiled_text(fn, q, k, v, table, lens),
                  f"{what}: no Mosaic kernel")
            compare(what, fn(q, k, v, table, lens), ref, exact,
                    ATTN_ATOL[jnp.dtype(dt).name])

    # quantized pools: codes + per-(page, head) scales, the newest partial
    # page exact in the tail ring; the reference reads a dense pool of the
    # dequantized pages with the tail page's exact rows
    def quantize(pool, n_bits):
        se = scale_exponent(jnp.asarray(pool[:, 0]), axis=-1)
        codes = quantize_page_codes(jnp.asarray(pool), se[:, None, :, None],
                                    n_bits)
        return codes, se.astype(jnp.int32)

    for n_bits in (4, 8):
        kc, ks = quantize(k32, n_bits)
        vc, vs = quantize(v32, n_bits)
        k_ref = np.asarray(dequantize_page_codes(kc, ks[:, None, :, None],
                                                 n_bits)).copy()
        v_ref = np.asarray(dequantize_page_codes(vc, vs[:, None, :, None],
                                                 n_bits)).copy()
        ring = 2 * page_len
        k_tail = np.zeros((b, ring + 1, g, d), np.float32)
        v_tail = np.zeros((b, ring + 1, g, d), np.float32)
        tab_h = np.asarray(table)
        for i, ln in enumerate(lengths):
            tb = max(int(ln) - 1, 0) // page_len
            pg = tab_h[i, tb]
            half = (tb % 2) * page_len
            k_tail[i, half:half + page_len] = k32[pg]
            v_tail[i, half:half + page_len] = v32[pg]
            k_ref[pg], v_ref[pg] = k32[pg], v32[pg]
        args = (jnp.asarray(q32), kc.astype(code_dtype(n_bits)), ks,
                vc.astype(code_dtype(n_bits)), vs, jnp.asarray(k_tail),
                jnp.asarray(v_tail), table, lens)
        ref = ref_attn(jnp.asarray(q32), jnp.asarray(k_ref),
                       jnp.asarray(v_ref))
        exact = ref_f64(q32, k_ref, v_ref)
        for splits in (1, 2):
            fn = functools.partial(paged_decode_attention_quant,
                                   n_bits=n_bits, splits=splits,
                                   interpret=False)
            what = f"paged attention q{n_bits} pool splits={splits}"
            check("tpu_custom_call" in compiled_text(fn, *args),
                  f"{what}: no Mosaic kernel")
            compare(what, fn(*args), ref, exact, ATTN_ATOL["float32"])

    # decode activations (8 rows) whose log2 exponents span the whole 4-bit
    # range, pruned values included
    mag = np.exp2(rng.integers(-9, 8, (8, cfg.d_ff))) * rng.uniform(
        1, 1.4, (8, cfg.d_ff))
    xs = jnp.asarray(mag * rng.choice([-1.0, 1.0], mag.shape), jnp.float32)
    x = xs[:, :cfg.d_model]
    fn = functools.partial(log2_quantize_pallas, interpret=False)
    check("tpu_custom_call" in compiled_text(fn, x),
          "log2quant: no Mosaic kernel")
    exp_k, sign_k = fn(x)
    q_ref = log2_quantize(x)
    check(np.array_equal(np.asarray(exp_k), np.asarray(q_ref.exp))
          and np.array_equal(np.asarray(sign_k), np.asarray(q_ref.sign)),
          "log2quant kernel differs from core.logquant.log2_quantize")
    log(f"log2quant {tuple(x.shape)}: bit-equal to the jnp quantizer")

    # the bit-plane GEMMs of smollm's MLP (up: K=576, N=1536; down: K=1536,
    # N=576) on those activations, kernel and jnp shift-add reference each
    # against int64 arithmetic on the host
    for k, n in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        q = log2_quantize(xs[:, :k])
        w = rng.integers(-128, 128, (k, n)).astype(np.int8)
        planes = to_bitplanes(jnp.asarray(w))
        exact = _shift_add_int64(np.asarray(q.exp), np.asarray(q.sign), w)
        fn = functools.partial(bitplane_matmul_pallas, interpret=False)
        what = f"bit-plane GEMM 8x{k}x{n}"
        check("tpu_custom_call" in compiled_text(fn, q.exp, q.sign, planes),
              f"{what}: no Mosaic kernel")
        y_k = np.asarray(fn(q.exp, q.sign, planes))
        y_ref = np.asarray(jax.jit(shiftadd_matmul_bitplane)(q, planes))
        log(f"{what}: kernel {'==' if np.array_equal(y_k, exact) else '!='} "
            f"int64, jnp reference "
            f"{'==' if np.array_equal(y_ref, exact) else '!='} int64 (max "
            f"|ref - int64| = {np.max(np.abs(y_ref - exact))})")
        check(np.array_equal(y_k, exact) and np.array_equal(y_ref, exact),
              f"{what}: not bit-equal to exact shift-add arithmetic")


def _shift_add_int64(e, s, w, n_bits: int = 4):
    """``sum_k s[m,k] * ArithShift(w[k,n], e[m,k])`` in int64 (the kernel's
    definition; the zero sentinel contributes nothing)."""
    import numpy as np

    e, s, w = (np.asarray(a, np.int64) for a in (e, s, w))
    out = np.zeros((e.shape[0], w.shape[1]), np.int64)
    for m in range(e.shape[0]):
        em = e[m][:, None]
        t = np.where(em >= 0, w << np.maximum(em, 0), w >> np.maximum(-em, 0))
        out[m] = (np.where(em == -(1 << (n_bits - 1)), 0, t)
                  * s[m][:, None]).sum(0)
    return out


# ---------------------------------------------------------------------------
# c. serving
# ---------------------------------------------------------------------------

SERVE_FLAGS = ["--arch", "smollm-135m", "--continuous", "--paged",
               "--chunked", "--attn-kernel", "--requests", "16",
               "--max-slots", "8", "--prompt-len", "16",
               "--new-tokens", "16"]


def _check_results(results, cfg, n, max_new, what):
    check(len(results) == n, f"{what}: {len(results)} results for {n} "
                             f"requests")
    for r in results:
        check(r.finish_reason == "length" and len(r.tokens) == max_new,
              f"{what}: request {r.rid} ended {r.finish_reason!r} with "
              f"{len(r.tokens)} tokens ({r.error})")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"{what}: request {r.rid} emitted an out-of-vocab token")


def phase_serving() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import (build_parser, build_serve_config,
                                    continuous_trace, serve_continuous)
    from repro.launch.shardings import init_params_sharded
    from repro.models.model import forward
    from repro.models.quantize import quantize_model_params

    args = build_parser().parse_args(SERVE_FLAGS)
    cfg = get_config(args.arch)
    params = init_params_sharded(jax.random.PRNGKey(args.seed), cfg,
                                 make_serve_mesh("host"))
    config = build_serve_config(args)
    check(config.paged and config.chunked == "auto"
          and config.attn_kernel == "pallas", f"unexpected config {config}")
    prompts = continuous_trace(cfg, config, requests=args.requests,
                               prompt_len=args.prompt_len, seed=args.seed)
    log(f"serving {cfg.name} (d={cfg.d_model}, {cfg.n_layers} layers, "
        f"vocab {cfg.vocab_size}): {len(prompts)} requests, prompts "
        f"{min(map(len, prompts))}..{max(map(len, prompts))} tokens")

    results, sched, dt = serve_continuous(
        cfg, params, config, prompts, max_new=args.new_tokens,
        keep_first_logits=True)
    _check_results(results, cfg, len(prompts), args.new_tokens, "float")
    log(f"float: {sum(len(r.tokens) for r in results)} tokens in {dt:.1f} s "
        f"(incl. compile, not a measurement); programs "
        f"{sched.compile_stats()}")

    # the dense read path (no pool, no kernel) over prompt + served tokens,
    # for four bucket-admitted requests: their first-token logits match it,
    # and every served token is its argmax or a bf16 near-tie of it.  One
    # right-padded batch: causal attention keeps pads out of every position
    probes = sorted(sched.first_logits.items())[:4]
    check(len(probes) == 4, f"only {len(probes)} first-token logits recorded")
    seqs = [np.concatenate([prompts[rid], results[rid].tokens])
            for rid, _ in probes]
    width = max(map(len, seqs))
    batch = np.zeros((len(seqs), width), np.int32)
    for i, sq in enumerate(seqs):
        batch[i, :len(sq)] = sq
    dense = np.asarray(jax.jit(lambda p, t: forward(cfg, p, tokens=t)[0])(
        params, jnp.asarray(batch)), np.float32)
    first_err = gap = 0.0
    for i, (rid, got) in enumerate(probes):
        n0 = len(prompts[rid])
        ref = dense[i, n0 - 1]
        first_err = max(first_err, float(np.max(np.abs(got - ref)))
                        / float(np.max(np.abs(ref))))
        for j, tok in enumerate(results[rid].tokens):
            row = dense[i, n0 - 1 + j]
            gap = max(gap, float(row.max() - row[tok])
                      / float(np.max(np.abs(row))))
    log(f"{len(probes)} requests vs the dense path: first-token logits "
        f"max |diff| / max |ref| = {first_err:.3e}; served tokens' logit gap "
        f"to the dense argmax, max / max |logit| = {gap:.3e} (tol "
        f"{LOGIT_RTOL} each)")
    check(first_err <= LOGIT_RTOL, f"first-token logits differ by "
                                   f"{first_err:.3e} of max |ref|")
    check(gap <= LOGIT_RTOL, f"a served token trails the dense argmax by "
                             f"{gap:.3e} of max |logit|")

    qparams = quantize_model_params(cfg, params)
    quant_tokens = {}
    for backend in ("pallas", "xla"):
        qargs = build_parser().parse_args(
            SERVE_FLAGS + ["--quant", "--quant-backend", backend])
        qconfig = build_serve_config(qargs)
        results, sched, dt = serve_continuous(
            cfg, qparams, qconfig, prompts, max_new=args.new_tokens)
        _check_results(results, cfg, len(prompts), args.new_tokens,
                       f"quant {backend}")
        quant_tokens[backend] = [r.tokens for r in results]
        log(f"quant {backend}: {sum(len(r.tokens) for r in results)} tokens "
            f"in {dt:.1f} s (incl. compile, not a measurement)")
    same = sum(int(a == b) for tp, tx in zip(quant_tokens["pallas"],
                                             quant_tokens["xla"])
               for a, b in zip(tp, tx))
    log(f"quant pallas vs xla: {same}/{len(prompts) * args.new_tokens} "
        f"tokens equal")
    check(quant_tokens["pallas"] == quant_tokens["xla"],
          "quantized serving: pallas and xla backends emit different tokens")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

FOUR_CHIP_FLAGS = ["--arch", "qwen2.5-14b", "--continuous", "--paged",
                   "--chunked", "--attn-kernel", "--requests", "16",
                   "--max-slots", "8", "--prompt-len", "16",
                   "--new-tokens", "8"]
PARITY_LAYERS = 4
# the 4-layer parity cut runs in f32, so that 1 chip and the 1x4 mesh differ
# only by the order of the row-parallel sums.  The quantized path's GEMMs
# are exact integers, so its logits agree to the bit; the float GEMMs take
# the TPU's default f32 matmul precision, which rounds operands to bf16, so
# an order difference upstream surfaces at bf16 resolution (2^-8) and the
# tolerance is a few bf16 roundings
PARITY_RTOL = {"float": 2e-2, "quant pallas": 1e-2}


def _serve_logged(cfg, params, config, prompts, max_new, mesh, what):
    """Serve ``prompts``; return each request's tokens and the recorded
    first-token logits."""
    from repro.launch.serve import serve_continuous

    results, sched, dt = serve_continuous(cfg, params, config, prompts,
                                          max_new=max_new, mesh=mesh,
                                          keep_first_logits=True)
    _check_results(results, cfg, len(prompts), max_new, what)
    log(f"{what}: {len(results)} requests, "
        f"{sum(len(r.tokens) for r in results)} tokens in {dt:.1f} s (incl. "
        f"compile, not a measurement); programs {sched.compile_stats()}")
    return [r.tokens for r in results], sched.first_logits


def _compare_runs(what, one, on_mesh, tol):
    """First-token logits and tokens of the 1x4 mesh against one chip."""
    import numpy as np

    (tok1, lg1), (tokm, lgm) = one, on_mesh
    common = sorted(set(lg1) & set(lgm))
    check(len(common) >= 2, f"{what}: {len(common)} comparable first-token "
                            f"logits")
    worst = max((float(np.max(np.abs(lgm[r] - lg1[r])))
                 / float(np.max(np.abs(lg1[r]))) for r in common),
                default=float("inf"))
    same = sum(int(a == b) for t1, tm in zip(tok1, tokm)
               for a, b in zip(t1, tm))
    total = sum(len(t) for t in tok1)
    log(f"{what}: first-token logits of {len(common)} requests, 1x4 mesh vs "
        f"1 chip: max |diff| / max |ref| = {worst:.3e} (tol {tol}); token "
        f"match {same}/{total} = {same / total:.3f}")
    check(worst <= tol, f"{what}: mesh logits differ by {worst:.3e}")


def _quantize_donated(cfg, params, mesh):
    """``quantize_model_params`` as one program that takes over the float
    weights' buffers: run eagerly, the bit-plane temporaries of a 4-layer
    stack of 5120x13824 weights (int32, ~9 GB) do not fit beside the f32
    weights on one chip."""
    import jax

    from repro.launch.shardings import params_shardings
    from repro.models.quantize import quantize_model_params

    def quantize(p):
        return quantize_model_params(cfg, p, pack=True)

    out = (None if mesh is None else
           params_shardings(mesh, jax.eval_shape(quantize, params),
                            fsdp=False))
    return jax.jit(quantize, donate_argnums=0, out_shardings=out)(params)


def phase_four_chips() -> None:
    import gc

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import (build_parser, build_serve_config,
                                    continuous_trace)
    from repro.launch.shardings import init_params_sharded
    from repro.models.model import init_params

    check(jax.device_count() == 4, f"{jax.device_count()} devices, not 4")
    mesh = make_serve_mesh("1x4")
    check(dict(mesh.shape) == {"data": 1, "model": 4},
          f"mesh shape {dict(mesh.shape)}")
    args = build_parser().parse_args(FOUR_CHIP_FLAGS)
    qargs = build_parser().parse_args(
        FOUR_CHIP_FLAGS + ["--quant", "--quant-backend", "pallas"])
    cfg = get_config(args.arch)
    configs = {"float": build_serve_config(args),
               "quant pallas": build_serve_config(qargs)}
    prompts = continuous_trace(cfg, configs["float"], requests=args.requests,
                               prompt_len=args.prompt_len, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)

    # parity: the published width cut to PARITY_LAYERS layers, served on
    # one of the four chips, then on the 1x4 mesh (one copy of the weights
    # on the chips at a time)
    cut = cfg.replace(n_layers=PARITY_LAYERS, dtype=jnp.float32)
    runs = {}
    for where in ("1 chip", "1x4 mesh"):
        if where == "1 chip":
            params, m = jax.jit(lambda k: init_params(k, cut))(key), None
        else:
            params, m = init_params_sharded(key, cut, mesh), mesh
        for mode, config in configs.items():
            if mode != "float":
                params = _quantize_donated(cut, params, m)
            runs[where, mode] = _serve_logged(
                cut, params, config, prompts, args.new_tokens, m,
                f"{cut.name} x{PARITY_LAYERS} layers f32 {mode} on {where}")
            gc.collect()            # the scheduler's pool goes with it
        del params
    for mode in configs:
        _compare_runs(f"{cut.name} x{PARITY_LAYERS} layers f32 {mode}",
                      runs["1 chip", mode], runs["1x4 mesh", mode],
                      PARITY_RTOL[mode])

    # the whole model: ~29.5 GB of bf16 weights, built sharded on the mesh
    params = init_params_sharded(key, cfg, mesh)
    _serve_logged(cfg, params, configs["float"], prompts, args.new_tokens,
                  mesh, f"{cfg.name} ({cfg.n_layers} layers, d={cfg.d_model}) "
                        f"on the 1x4 mesh")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from repro.launch.runtime import enable_compile_cache
    enable_compile_cache()

    t0 = time.perf_counter()
    device = device_info(args.chips)
    if FAILURES:
        return 1
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_kernels()
        log(f"kernels done ({time.perf_counter() - t0:.0f} s so far)")
        phase_serving()
    if FAILURES:
        log(f"{len(FAILURES)} check(s) failed")
        return 1
    log(f"all phases ok ({time.perf_counter() - t0:.0f} s incl. compile)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
