"""DecoderModel: one machinery for all 10 assigned architectures.

A model is a periodic ``pattern`` of block kinds (length = period ``P``)
repeated ``n_layers / P`` times.  Parameters are stored *stacked over
repeats* (leading dim ``R``) and executed with ``lax.scan`` over repeats,
with the period unrolled inside the scan body — true layer order, small HLO,
fast 512-device SPMD compiles, and remat-at-period granularity.

Block kinds: ``attn`` | ``attn_moe`` | ``mamba`` | ``mamba_moe``.
Frontends (audio/vision) are stubs per the assignment: ``input_specs()``
supplies precomputed frame/patch embeddings.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.shiftadd import as_quant_ctx
from repro.models import moe as moe_lib
from repro.models import ssd as ssd_lib
from repro.models.attention import (KVCache, PagedKVCache,
                                    QuantPagedKVCache, attention)
from repro.models.layers import (dense, dense_init, embed_init, rms_norm,
                                 swiglu)
from repro.models.sharding import shard

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    d_ff: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 128
    pattern: Tuple[str, ...] = ("attn",)
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    conv_width: int = 4
    ssd_chunk: int = 256
    # frontends
    frontend: str = "none"            # none | audio_stub | vision_stub
    n_image_tokens: int = 0
    # execution
    dtype: Any = jnp.bfloat16
    cache_dtype: Any = None           # None -> io dtype; f8 halves KV residency
    kv_chunk: int = 1024
    remat: str = "full"               # none | full | dots
    # paged decode attention: "off" = dense pool[table] gather + masked
    # einsum; "pallas" = the fused table-walk kernel
    # (kernels/paged_attention) with paged_attn_splits-way split-KV
    # flash-decode.  Only consulted on the PagedKVCache decode path.
    paged_attn_kernel: str = "off"    # off | pallas
    paged_attn_splits: int = 1
    # log2-quantized KV pages (DESIGN.md §Quantized KV pages): the paged
    # pool stores packed core/logquant codes + per-page power-of-two scale
    # exponents instead of full-precision rows; a dense f32 tail ring keeps
    # the newest (partial) page exact.  Only consulted by init_paged_pool /
    # the PagedKVCache paths.
    kv_quant: bool = False
    kv_bits: int = 4
    # attention class: 'full' is quadratic -> long_500k is skipped for these
    # (DESIGN.md §Skips); SSM/hybrid run it.
    sub_quadratic: bool = False

    @property
    def repeats(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, \
            f"{self.n_layers} layers not divisible by period {len(self.pattern)}"
        return self.n_layers // len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_mlp(key, cfg: ModelConfig, moe: bool) -> Params:
    dt = cfg.dtype
    d = cfg.d_model
    if not moe:
        k1, k2, k3 = jax.random.split(key, 3)
        return {"gate": dense_init(k1, d, cfg.d_ff, dt),
                "up": dense_init(k2, d, cfg.d_ff, dt),
                "down": dense_init(k3, cfg.d_ff, d, dt)}
    ks = jax.random.split(key, 5)
    ffe = cfg.moe_d_ff or cfg.d_ff
    e = cfg.n_experts
    p: Params = {
        "router": dense_init(ks[0], d, e, jnp.float32, scale=0.02),
        "experts": {
            "gate": dense_init(ks[1], e * d, ffe, dt).reshape(e, d, ffe),
            "up": dense_init(ks[2], e * d, ffe, dt).reshape(e, d, ffe),
            "down": dense_init(ks[3], e * ffe, d, dt).reshape(e, ffe, d),
        },
    }
    if cfg.n_shared_experts:
        ffs = ffe * cfg.n_shared_experts
        s1, s2, s3 = jax.random.split(ks[4], 3)
        p["shared"] = {"gate": dense_init(s1, d, ffs, dt),
                       "up": dense_init(s2, d, ffs, dt),
                       "down": dense_init(s3, ffs, d, dt)}
    return p


def _init_attn(key, cfg: ModelConfig) -> Params:
    dt = cfg.dtype
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p: Params = {
        "ln1": jnp.ones((d,), dt),
        "wq": dense_init(ks[0], d, h * hd, dt),
        "wk": dense_init(ks[1], d, hkv * hd, dt),
        "wv": dense_init(ks[2], d, hkv * hd, dt),
        "wo": dense_init(ks[3], h * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _init_mamba(key, cfg: ModelConfig) -> Params:
    dt = cfg.dtype
    d = cfg.d_model
    h, pdim, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner = h * pdim
    ks = jax.random.split(key, 9)
    w = cfg.conv_width

    def conv_init(k, c):
        return (jax.random.normal(k, (w, c), jnp.float32) * 0.2).astype(dt)

    # separate, shard-aligned projections (see models/ssd.py §Perf note)
    return {
        "ln1": jnp.ones((d,), dt),
        "wz": dense_init(ks[0], d, d_inner, dt),
        "wx": dense_init(ks[1], d, d_inner, dt),
        "wb": dense_init(ks[2], d, n, dt),
        "wc": dense_init(ks[3], d, n, dt),
        "wdt": dense_init(ks[4], d, h, dt),
        "conv_wx": conv_init(ks[5], d_inner),
        "conv_bx": jnp.zeros((d_inner,), dt),
        "conv_wb": conv_init(ks[6], n),
        "conv_bb": jnp.zeros((n,), dt),
        "conv_wc": conv_init(ks[7], n),
        "conv_bc": jnp.zeros((n,), dt),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.zeros((h,), jnp.float32),          # A = -1
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((d_inner,), dt),
        "out_proj": dense_init(ks[8], d_inner, d, dt),
    }


def _init_block(key, cfg: ModelConfig, kind: str) -> Params:
    base, moe = (kind.split("_") + [""])[:2]
    k1, k2 = jax.random.split(key)
    if base == "attn":
        p = _init_attn(k1, cfg)
    elif base == "mamba":
        p = _init_mamba(k1, cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if moe == "moe":
        p["ln2"] = jnp.ones((cfg.d_model,), cfg.dtype)
        p["mlp"] = _init_mlp(k2, cfg, moe=True)
    elif base == "attn" or cfg.d_ff:
        p["ln2"] = jnp.ones((cfg.d_model,), cfg.dtype)
        p["mlp"] = _init_mlp(k2, cfg, moe=False)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    blocks = []
    for i, kind in enumerate(cfg.pattern):
        layer_keys = jax.random.split(keys[i], cfg.repeats)
        blocks.append(jax.vmap(
            lambda k, kind=kind: _init_block(k, cfg, kind))(layer_keys))
    params: Params = {
        "embed": embed_init(keys[-3], cfg.vocab_size, cfg.d_model, cfg.dtype),
        "blocks": tuple(blocks),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-2], cfg.d_model, cfg.vocab_size,
                                       cfg.dtype, scale=0.02)
    if cfg.frontend == "vision_stub":
        params["img_proj"] = dense_init(keys[-1], cfg.d_model, cfg.d_model,
                                        cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None, per_slot: bool = False) -> Params:
    """Stacked (over repeats) per-period-position cache trees.

    ``per_slot=True`` makes ``length`` a ``(batch,)`` vector — one valid
    length per batch row — which is what the continuous-batching slot pool
    needs (``serving/scheduler.py``): every cache consumer accepts either the
    scalar or the per-row form.
    """
    if dtype is None:
        dtype = cfg.cache_dtype or cfg.dtype
    layers = []
    for kind in cfg.pattern:
        base = kind.split("_")[0]
        if base == "attn":
            c = {"k": jnp.zeros((cfg.repeats, batch, max_len, cfg.n_kv_heads,
                                 cfg.head_dim), dtype),
                 "v": jnp.zeros((cfg.repeats, batch, max_len, cfg.n_kv_heads,
                                 cfg.head_dim), dtype)}
        else:
            st = ssd_lib.mamba2_init_state(batch, cfg, dtype)
            c = {"ssm": jnp.broadcast_to(st.ssm, (cfg.repeats,) + st.ssm.shape),
                 "conv": jnp.broadcast_to(st.conv, (cfg.repeats,) + st.conv.shape)}
        layers.append(c)
    length = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    return {"layers": tuple(layers), "length": length}


def init_paged_pool(cfg: ModelConfig, batch: int, max_len: int,
                    n_pages: int, page_len: int, dtype=None) -> Params:
    """Paged slot-pool caches (``serving/scheduler.py`` ``paged=True``).

    Attention KV lives in a shared page pool ``(R, n_pages, page_len, G,
    D)`` indexed through a host-side per-slot page table instead of a
    dense ``(R, B, max_len, ...)`` slab — page 0 is the reserved trash
    page (``serving.kvpool``).  SSM/conv recurrent state cannot be paged
    (a recurrence has no per-position rows to alias) and keeps the dense
    per-slot layout; ``length`` is per-slot like ``init_caches(per_slot=
    True)``.  ``max_len`` must be a multiple of ``page_len`` so the
    gathered per-slot view ``(B, blocks * page_len, ...)`` matches the
    dense slab shape exactly (the bit-equality bar).

    ``cfg.kv_quant=True`` swaps the full-precision K/V pools for the
    log2-compressed page format (DESIGN.md §Quantized KV pages): packed
    wire codes ``{k,v}_codes (R, n_pages, page_len, G, D)``
    (``core.logquant.code_dtype(cfg.kv_bits)``), per-page power-of-two
    scale exponents ``{k,v}_scale (R, n_pages, G)`` int32, and a dense
    per-slot tail ring ``{k,v}_tail (R, B, 2*page_len + 1, G, D)`` that
    holds each slot's newest two pages exactly (row ``2*page_len`` is the
    junk bin for masked writes).  Two pages — not one — so a page-boundary
    junk write from an inactive slot (frozen length ≡ 0 mod page_len)
    lands in the ring slot of a position two pages back, never clobbering
    a row the overlay still reads.
    """
    if max_len % page_len:
        raise ValueError(f"max_len={max_len} must be a multiple of "
                         f"page_len={page_len}")
    if dtype is None:
        dtype = cfg.cache_dtype or cfg.dtype
    layers = []
    for kind in cfg.pattern:
        base = kind.split("_")[0]
        if base == "attn" and cfg.kv_quant:
            from repro.core.logquant import code_dtype
            ct = code_dtype(cfg.kv_bits)
            kv_shape = (cfg.repeats, n_pages, page_len,
                        cfg.n_kv_heads, cfg.head_dim)
            tail_shape = (cfg.repeats, batch, 2 * page_len + 1,
                          cfg.n_kv_heads, cfg.head_dim)
            c = {"k_codes": jnp.zeros(kv_shape, ct),
                 "v_codes": jnp.zeros(kv_shape, ct),
                 "k_scale": jnp.zeros((cfg.repeats, n_pages,
                                       cfg.n_kv_heads), jnp.int32),
                 "v_scale": jnp.zeros((cfg.repeats, n_pages,
                                       cfg.n_kv_heads), jnp.int32),
                 "k_tail": jnp.zeros(tail_shape, dtype),
                 "v_tail": jnp.zeros(tail_shape, dtype)}
        elif base == "attn":
            c = {"k": jnp.zeros((cfg.repeats, n_pages, page_len,
                                 cfg.n_kv_heads, cfg.head_dim), dtype),
                 "v": jnp.zeros((cfg.repeats, n_pages, page_len,
                                 cfg.n_kv_heads, cfg.head_dim), dtype)}
        else:
            st = ssd_lib.mamba2_init_state(batch, cfg, dtype)
            c = {"ssm": jnp.broadcast_to(st.ssm, (cfg.repeats,) + st.ssm.shape),
                 "conv": jnp.broadcast_to(st.conv,
                                          (cfg.repeats,) + st.conv.shape)}
        layers.append(c)
    return {"layers": tuple(layers),
            "length": jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _apply_block(cfg: ModelConfig, kind: str, p: Params, x, positions,
                 cache, cache_len, quant, valid_len=None, chunk_valid=None,
                 page_table=None):
    base = kind.split("_")[0]
    is_moe = kind.endswith("_moe")
    x = shard(x, "btd")                     # keep the scan carry SP-sharded
    # the mixer ("attn" | "mamba") and the MLP each run under a named
    # scope: HLO metadata only, so a profile splits a layer's device
    # time between them (DESIGN.md §Observability)
    with jax.named_scope(base):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if base == "attn":
            if cache is None:
                kv = None
            elif page_table is not None and "k_codes" in cache:
                # log2-quantized page pool: packed codes + per-page
                # scales + dense tail ring (models/attention.py quantized
                # paths)
                kv = QuantPagedKVCache(
                    k_codes=cache["k_codes"], v_codes=cache["v_codes"],
                    k_scale=cache["k_scale"], v_scale=cache["v_scale"],
                    k_tail=cache["k_tail"], v_tail=cache["v_tail"],
                    page_table=page_table, length=cache_len)
            elif page_table is not None:
                # paged slot pool: this layer's KV is a page pool indexed
                # by the shared host-built page table (models/attention.py)
                kv = PagedKVCache(k=cache["k"], v=cache["v"],
                                  page_table=page_table, length=cache_len)
            else:
                kv = KVCache(k=cache["k"], v=cache["v"], length=cache_len)
            out, new_kv = attention(p, h, positions, cfg, cache=kv,
                                    quant=quant, chunk_valid=chunk_valid)
            if new_kv is None:
                new_cache = None
            elif isinstance(new_kv, QuantPagedKVCache):
                new_cache = {"k_codes": new_kv.k_codes,
                             "v_codes": new_kv.v_codes,
                             "k_scale": new_kv.k_scale,
                             "v_scale": new_kv.v_scale,
                             "k_tail": new_kv.k_tail,
                             "v_tail": new_kv.v_tail}
            else:
                new_cache = {"k": new_kv.k, "v": new_kv.v}
        else:
            st = None if cache is None else ssd_lib.SSMState(
                ssm=cache["ssm"], conv=cache["conv"])
            # a chunk's per-row valid count doubles as the SSM pad mask:
            # pad tokens get dt = 0 (state passes through untouched) and the
            # rolling conv window re-anchors at the real-token boundary —
            # the same masking bucketed prefill uses, applied mid-prompt
            out, new_st = ssd_lib.mamba2_block(
                p, h, cfg, state=st, quant=quant,
                valid_len=(chunk_valid if chunk_valid is not None
                           else valid_len))
            new_cache = None if new_st is None else {
                "ssm": new_st.ssm, "conv": new_st.conv}
        # hint the projection output to the residual sharding *before* the
        # add so GSPMD emits reduce-scatter (SP) rather than all-reduce +
        # slice
        out = shard(out, "btd")
        x = x + out
    if "mlp" in p:
        with jax.named_scope("mlp"):
            h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
            if is_moe:
                y = moe_lib.moe_apply(p["mlp"], h2, cfg, quant=quant)
            else:
                y = swiglu(p["mlp"], h2, quant=quant)
            y = shard(y, "btd")
            x = x + y
            x = shard(x, "btd")
    return x, new_cache


def forward(cfg: ModelConfig, params: Params, *,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            image_embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            caches: Optional[Params] = None,
            quant=False,
            return_stats: bool = False,
            valid_len: Optional[jnp.ndarray] = None,
            chunk_valid: Optional[jnp.ndarray] = None,
            page_table: Optional[jnp.ndarray] = None):
    """Returns (logits, new_caches). ``caches`` enables decode/prefill mode.

    ``quant`` (bool | str | QuantCtx) routes eligible projections through the
    QeiHaN shift-add path.  With ``return_stats=True`` a third element is
    returned: ``{"plane_fetched", "plane_total", "plane_traffic_fraction"}``,
    the weight-plane HBM-traffic accounting summed over every quantized
    projection of the call (the decode-time image of the paper's §VI
    memory-access savings; zeros when ``quant`` is falsy).

    ``caches["length"]`` may be a scalar (whole-batch, the classic path) or a
    ``(B,)`` vector (per-slot lengths, continuous batching): positions, KV
    writes and attention masking all honor the per-row form.  ``valid_len``
    (``(B,)``, bucketed prefill only) marks rows ``>= valid_len[b]`` of the
    input as right-padding: SSM state/conv updates are masked so pad tokens
    neither decay nor feed the recurrent state (attention needs no mask —
    pads sit at causal positions after every real token).

    ``chunk_valid`` (``(B,)``, chunked prefill) marks the input as one
    right-padded *mid-prompt chunk* per row: earlier chunks already live in
    the caches, so attention writes only the real slab rows and attends over
    the cache (``models.attention`` chunk path), the SSM path applies the
    same ``valid_len`` pad masking, and the cache ``length`` advances by
    ``chunk_valid`` — not by the padded slab width ``s``.  A row with
    ``chunk_valid[b] == 0`` passes through the call with its cache
    bit-identical (decode/free slots ride along in the serve scheduler's
    mixed tick).  Mutually exclusive with ``valid_len``.
    """
    if valid_len is not None and chunk_valid is not None:
        raise ValueError("pass either valid_len (bucketed prefill) or "
                         "chunk_valid (chunked prefill), not both")
    if chunk_valid is not None and caches is None:
        raise ValueError("chunk_valid requires caches: a chunk appends to "
                         "resident earlier chunks")
    ctx = as_quant_ctx(quant)
    if embeds is not None:                       # audio stub: direct embeddings
        x = embeds.astype(cfg.dtype)
    else:
        x = params["embed"][tokens]
    if image_embeds is not None:                 # vision stub: prepend patches
        img = dense(params["img_proj"], image_embeds.astype(cfg.dtype))
        x = jnp.concatenate([img, x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        base = caches["length"] if caches is not None else 0
        if getattr(base, "ndim", 0):                 # per-slot (B,) lengths
            positions = base[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        else:
            positions = base + jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s))
    x = shard(x, "btd")
    cache_len = caches["length"] if caches is not None else None

    layer_caches = caches["layers"] if caches is not None else None

    def period_body(x, xs):
        lp, lc = xs
        # plane-traffic accounting: the collect list is created AND consumed
        # inside this body so its tracers never cross the scan boundary; the
        # per-period sums stream out as scan ys
        bctx = None if ctx is None else dataclasses.replace(
            ctx, collect=[] if return_stats else None)
        new_cs = []
        for i, kind in enumerate(cfg.pattern):
            c_i = None if lc is None else lc[i]
            x, nc = _apply_block(cfg, kind, lp[i], x, positions, c_i,
                                 cache_len, bctx, valid_len=valid_len,
                                 chunk_valid=chunk_valid,
                                 page_table=page_table)
            new_cs.append(nc)
        traffic = None
        if return_stats:
            coll = bctx.collect if bctx is not None else []
            zero = jnp.zeros((), jnp.float32)
            traffic = tuple(sum((c[j] for c in coll), zero) for j in range(4))
        return x, (tuple(new_cs), traffic)

    body = period_body
    if cfg.remat == "full":
        body = jax.checkpoint(period_body, prevent_cse=False)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            period_body, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    if layer_caches is None:
        def scan_body(x, lp):
            x, (_, traffic) = body(x, (lp, None))
            return x, traffic
        x, traffic = jax.lax.scan(scan_body, x, params["blocks"])
        new_caches = None
    # NB (§Perf, refuted hypothesis): carrying the stacked caches as scan
    # carry + in-place update triggers XLA copy-insertion of the FULL cache
    # buffer per layer (the carry is both sliced and updated in one
    # iteration) — measured 6.5x worse than xs/ys streaming, which reads and
    # writes each layer's cache exactly once per step.
    else:
        def scan_body(x, xs):
            return body(x, xs)
        x, (new_layer_caches, traffic) = jax.lax.scan(
            scan_body, x, (params["blocks"], layer_caches))
        # a chunk advances each row by its REAL token count, not the padded
        # slab width (chunk_valid == 0 rows stay put entirely)
        new_caches = {"layers": new_layer_caches,
                      "length": cache_len + (s if chunk_valid is None
                                             else chunk_valid)}

    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.matmul(x, head.astype(x.dtype))
        logits = shard(logits, "btv")
    if not return_stats:
        return logits, new_caches
    tile_f, tile_t, el_f, el_t = (jnp.sum(t) for t in traffic)
    stats = {"plane_fetched": tile_f, "plane_total": tile_t,
             "plane_traffic_fraction": tile_f / jnp.maximum(tile_t, 1.0),
             "element_traffic_fraction": el_f / jnp.maximum(el_t, 1.0)}
    return logits, new_caches, stats


# ---------------------------------------------------------------------------
# loss / accounting
# ---------------------------------------------------------------------------

def next_token_loss(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
                    quant: bool = False) -> jnp.ndarray:
    """Causal LM loss.  batch: tokens/embeds (+image_embeds), labels, mask."""
    logits, _ = forward(cfg, params,
                        tokens=batch.get("tokens"),
                        embeds=batch.get("embeds"),
                        image_embeds=batch.get("image_embeds"),
                        quant=quant)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:       # vision stub prepended tokens
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    lab = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    nll = lse - lab
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def param_count(cfg: ModelConfig) -> Dict[str, int]:
    """Analytic parameter counts (total & active) for roofline MODEL_FLOPS."""
    import math
    tree = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(tree))
    expert = 0
    for i, kind in enumerate(cfg.pattern):
        if kind.endswith("_moe"):
            blk = tree["blocks"][i]
            expert += sum(math.prod(l.shape)
                          for l in jax.tree.leaves(blk["mlp"]["experts"]))
    if cfg.n_experts:
        active = total - expert * (1 - cfg.experts_per_token / cfg.n_experts)
    else:
        active = total
    return {"total": int(total), "active": int(active)}
