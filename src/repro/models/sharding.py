"""Activation-sharding context for the model zoo.

Models place ``shard(x, kind)`` hints at the canonical Megatron/SP points;
the launcher configures which mesh axes those hints bind to (and their
sizes).  When no axes are configured (unit tests, single-device smoke runs)
the hints are no-ops, so the same model code runs everywhere.

Every hint is **divisibility-checked**: a dim whose size doesn't divide the
bound axis size stays unsharded (e.g. smollm's 9 heads under 16-way TP) —
this mirrors the param-rule fallback and avoids GSPMD involuntary
rematerialization/replication.

Kinds:
  ``btd``   — residual stream (batch, seq, d): batch on DP axes, seq on the
              TP axis (sequence parallelism) so scan-carried remat residuals
              are distributed.
  ``btf``   — MLP hidden (batch, seq, ff): ff on TP.
  ``bthd``  — attention heads (batch, seq, heads, head_dim): heads on TP.
  ``btv``   — logits (batch, seq, vocab): vocab on TP.
  ``ecd``   — MoE expert buffers (experts, capacity, d): experts on TP (EP).
  ``cache`` — KV cache (batch, seq, kv_heads, hd): seq on DP for
              long-context decode (batch=1 there), else batch on DP.
  ``pool``  — paged-KV page pool (pages, page_len, kv_heads, hd): pages
              on DP (the batch role for paged serving).
  ``bshp``/``bchll``/``bchpn`` — SSD tensors: ssm-heads on TP.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

_state = threading.local()

_KIND_LAYOUT = {
    # kind -> list of (role) per dim; roles: 'b' batch, 's' seq (SP),
    # 'm' model, None replicate
    "btd": ("b", "s", None),
    "btf": ("b", None, "m"),
    "bthd": ("b", None, "m", None),
    "btv": ("b", None, "m"),
    "ecd": ("m", None, None),
    "bshp": ("b", None, "m", None),
    "bchll": ("b", None, "m", None, None),
    "bchpn": ("b", None, "m", None, None),
    "cache": ("b", "cs", None, None),
    # paged KV page pool (pages, page_len, kv_heads, hd): pages on the DP
    # axes — the page dim plays the batch role (every slot's rows live in
    # its pages), and the in-page token dim is never sharded so (page,
    # offset) indexing needs no sharded-axis reshape
    "pool": ("b", None, None, None),
    # split-KV flash-decode partials (kernels/paged_attention): acc
    # (B, split, G, R, D) and the (m, l) statistics (B, split, G, R).  The
    # split axis rides the model axis — each model shard owns a contiguous
    # run of KV pages and its own partial softmax, and the cross-split
    # merge (ops.merge_split_softmax) is the only collective: a tiny
    # (B, G, R)-sized statistic reduce instead of an all-gathered cache
    # (launch.shardings.split_kv_specs is the jit-boundary image)
    "kvsplit": ("b", "m", None, None, None),
    "kvsplit_stat": ("b", "m", None, None),
    # channels-REPLICATED (B, S, C): used with force=True to pin tensors
    # whose channel axis is about to be concat/split (the mamba conv window)
    "btc": ("b", None, None),
}


def _axes():
    return getattr(_state, "axes", None)


@contextlib.contextmanager
def mesh_axes(batch: Sequence[str] | str | None = ("data",),
              model: Optional[str] = "model",
              seq_shard: bool = True,
              cache_seq_axis: Optional[str] = None,
              sizes: Optional[Dict[str, int]] = None,
              mesh=None,
              ep_axis: Optional[str] = None):
    """Bind sharding hints to mesh axis names for the enclosed scope.

    ``sizes`` maps axis name -> size for divisibility checks (pass
    ``dict(mesh.shape)``); without it hints are applied unchecked.
    ``mesh`` (optional) enables shard_map-based blocks (manual-EP MoE);
    ``ep_axis`` names the expert-parallel axis (defaults to ``model``).
    """
    prev = _axes()
    batch_t = tuple(batch) if isinstance(batch, (tuple, list)) else (
        (batch,) if batch else ())
    if mesh is not None and sizes is None:
        sizes = dict(mesh.shape)
    _state.axes = dict(batch=batch_t, model=model, seq_shard=seq_shard,
                       cache_seq_axis=cache_seq_axis, sizes=sizes or {},
                       mesh=mesh, ep_axis=ep_axis if ep_axis else model)
    try:
        yield
    finally:
        _state.axes = prev


def current() -> Optional[dict]:
    """The active mesh-axes binding (None outside any mesh_axes scope)."""
    return _axes()


def _fits(dim_size: int, axis, sizes: Dict[str, int]) -> bool:
    if axis is None:
        return False
    names = axis if isinstance(axis, tuple) else (axis,)
    total = 1
    for n in names:
        total *= sizes.get(n, 1)
    if total <= 1:
        return False
    return dim_size % total == 0


def spec_for(kind: str, shape, force: bool = False) -> Optional[P]:
    ax = _axes()
    if ax is None:
        return None
    layout = _KIND_LAYOUT.get(kind)
    if layout is None:
        raise ValueError(f"unknown sharding kind {kind!r}")
    if len(layout) != len(shape):
        return None
    sizes = ax["sizes"]
    b = ax["batch"] if ax["batch"] else None
    m = ax["model"]
    entries = []
    for role, dim in zip(layout, shape):
        target = None
        if role == "b":
            target = b
        elif role == "m":
            target = m
        elif role == "s":
            target = m if ax["seq_shard"] else None
        elif role == "cs":
            # KV-cache sequence dim: explicit long-context axis, else the TP
            # axis (kv heads rarely divide 16-way TP; the seq dim always does)
            target = ax["cache_seq_axis"] or m
        if target is not None and (not sizes or _fits(dim, target, sizes)):
            entries.append(target)
        else:
            entries.append(None)
    if all(e is None for e in entries) and not force:
        return None
    return P(*entries)


def shard(x: jax.Array, kind: str, force: bool = False) -> jax.Array:
    """Sharding hint; a no-op outside a :func:`mesh_axes` scope.

    ``force=True`` applies the constraint even when every dim falls back to
    replicated — an all-``None`` spec is normally skipped as useless, but it
    is exactly what pins a tensor REPLICATED against GSPMD's propagation
    choices.  Rope inputs need this: jax 0.4.37's CPU SPMD backend
    miscompiles split/concat along a sharded axis (partially-replicated
    meshes only — see tests/test_serve_sharded.py), and head-dim replication
    before rope is the standard Megatron layout on TPU anyway.
    """
    s = spec_for(kind, x.shape, force=force)
    if s is None:
        return x
    return _constrain(x, s)


def replicate(x: jax.Array) -> jax.Array:
    """Force ``x`` fully replicated (any rank); a no-op outside a
    :func:`mesh_axes` scope.  The blunt instrument behind the CPU-SPMD
    hazard rule (see :func:`shard`): tensors about to be concatenated or
    split along an axis that param rules may have sharded — e.g. the mamba
    conv weights — get pinned replicated first."""
    ax = _axes()
    if ax is None or not ax["sizes"]:
        return x
    return _constrain(x, P(*([None] * getattr(x, "ndim", 0))))


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """``with_sharding_constraint`` on the bound mesh (a bare spec needs an
    enclosing ``with mesh``).  A failing constraint raises: a hint that
    silently does nothing would leave GSPMD free to pick a layout the
    rules never meant."""
    mesh = _axes()["mesh"]
    if mesh is not None:
        spec = jax.sharding.NamedSharding(mesh, spec)
    return jax.lax.with_sharding_constraint(x, spec)


def kernel_mesh():
    """``(mesh, batch axes, model axis)`` when the bound mesh spans more
    than one device, else None.  Mosaic kernels are not partitioned by
    GSPMD, so on such a mesh every Pallas kernel call runs per device
    inside ``shard_map`` (:func:`kernel_call`).  Resolved outside any
    ``jax.jit`` so the result can be a static argument."""
    ax = _axes()
    mesh = ax["mesh"] if ax is not None else None
    if mesh is None or mesh.size == 1:
        return None
    return mesh, tuple(ax["batch"]), ax["model"]


def kernel_call(fn, kmesh, in_layouts, out_layouts, *args,
                reduce_model: bool = False):
    """``fn(*args)``, per device under ``shard_map`` when ``kmesh`` (from
    :func:`kernel_mesh`) is set.

    Layouts give one role per dim — ``"b"`` the batch axes, ``"m"`` the
    model axis, None replicated.  A role binds its axis only where the axis
    size divides every input dim of that role; otherwise those dims stay
    replicated (GSPMD gathers them around the call).  ``reduce_model``
    sums the per-device results over the model axis (a GEMM whose
    contraction dim is model-sharded).  ``out_layouts`` is one layout, or
    a list of layouts for a kernel with several outputs."""
    if kmesh is None:
        return fn(*args)
    mesh, batch, model = kmesh
    sizes = {"b": int(np.prod([mesh.shape[a] for a in batch])),
             "m": mesh.shape[model] if model else 1}
    axis = {"b": (batch if len(batch) > 1 else batch[0]) if batch else None,
            "m": model}
    live = {r for r in ("b", "m") if sizes[r] > 1 and all(
        dim % sizes[r] == 0
        for lay, a in zip(in_layouts, args)
        for role, dim in zip(lay, a.shape) if role == r)}

    def spec(layout):
        return P(*(axis[r] if r in live else None for r in layout))

    multi = isinstance(out_layouts, list)

    def local(*xs):
        out = fn(*xs)
        if reduce_model and "m" in live:
            out = jax.lax.psum(out, model)
        return tuple(out) if multi else out

    out_specs = (tuple(spec(l) for l in out_layouts) if multi
                 else spec(out_layouts))
    return jax.shard_map(local, mesh=mesh,
                         in_specs=tuple(spec(l) for l in in_layouts),
                         out_specs=out_specs, check_vma=False)(*args)
