"""Mixture-of-Experts: sort-based capacity dispatch with two execution paths.

* **Local (pjit/single-device)** — the straightforward jnp formulation used
  for CPU smoke tests and small token counts (decode): top-k route, stable
  sort by expert, scatter into an (E, C, d) buffer, stacked-expert einsums,
  gather/combine.

* **Manual EP (shard_map)** — used under a mesh (``models.sharding.current``
  provides it) when experts divide the TP axis.  GSPMD cannot partition the
  dispatch scatter between token-sharded sources and expert-sharded buffers
  (it replicates — measured 9.5 TiB/chip all-reduce on deepseek-moe), so we
  do what production MoE systems do: each chip dispatches its *local* tokens
  into per-expert buffers, a tiled ``all_to_all`` over the EP axis regroups
  slots expert-major, local expert GEMMs run, and a second ``all_to_all``
  returns outputs to the token owners.  When tokens are replicated over the
  EP axis (decode without seq sharding) the combine is a ``psum`` instead.

Dense one-hot (GShard) dispatch is avoided entirely: its (tokens, E, C)
tensor is quadratic in tokens and infeasible at 1M-token train steps.

Shared experts (DeepSeekMoE) run densely on every token outside the routed
path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import sharding as shctx
from repro.models.layers import swiglu
from repro.models.sharding import shard


def topk_routing(router_w, x2d: jnp.ndarray, n_experts: int, k: int):
    """x2d: (G, d) -> gates (G, k) f32, ids (G, k) int32."""
    logits = jnp.matmul(x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))       # (G, E)
    gates, ids = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, ids


def _dispatch_tables(ids: jnp.ndarray, n_experts: int,
                     capacity: int) -> Tuple[jnp.ndarray, ...]:
    """Sort-based slot -> (expert, position) mapping with capacity drops.

    Returns (order, dest, keep): ``order`` sorts slots expert-major;
    ``dest`` is the row in the flattened (E*C) buffer (dropped -> E*C)."""
    gk = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_experts), side="left")
    pos = jnp.arange(gk) - starts[sorted_ids]
    keep = pos < capacity
    dest = jnp.where(keep, sorted_ids * capacity + pos, n_experts * capacity)
    return order, dest, keep


def _expert_ffn(buf: jnp.ndarray, experts, dtype) -> jnp.ndarray:
    hg = jnp.einsum("ecd,edf->ecf", buf, experts["gate"].astype(dtype))
    hu = jnp.einsum("ecd,edf->ecf", buf, experts["up"].astype(dtype))
    h = jax.nn.silu(hg.astype(jnp.float32)).astype(dtype) * hu
    return jnp.einsum("ecf,efd->ecd", h, experts["down"].astype(dtype))


def _route_local(p, x2d: jnp.ndarray, cfg) -> jnp.ndarray:
    g, d = x2d.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    capacity = min(int(g * k / e * cfg.capacity_factor) + 1, g)

    gates, ids = topk_routing(p["router"], x2d, e, k)
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    slot_token = jnp.repeat(jnp.arange(g), k)

    order, dest, keep = _dispatch_tables(flat_ids, e, capacity)
    xin = x2d[slot_token[order]]
    buf = jnp.zeros((e * capacity + 1, d), x2d.dtype).at[dest].set(xin)
    buf = shard(buf[:-1].reshape(e, capacity, d), "ecd")
    out_buf = shard(_expert_ffn(buf, p["experts"], x2d.dtype), "ecd")

    flat_out = out_buf.reshape(e * capacity, d)
    safe = jnp.clip(dest, 0, e * capacity - 1)
    slot_out = jnp.where(keep[:, None], flat_out[safe], 0.0)
    slot_out = slot_out * flat_gates[order][:, None].astype(x2d.dtype)
    return jnp.zeros((g, d), x2d.dtype).at[slot_token[order]].add(slot_out)


# ---------------------------------------------------------------------------
# manual EP via shard_map
# ---------------------------------------------------------------------------

def _route_ep_body(router, experts, x_loc, *, cfg, axis: str,
                   tokens_split: bool):
    """Runs per-chip inside shard_map.  x_loc: (b_loc, s_loc, d)."""
    m = jax.lax.axis_size(axis)
    col = jax.lax.axis_index(axis)
    b, s, d = x_loc.shape
    g = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    e_loc = e // m
    x2d = x_loc.reshape(g, d)
    gates, ids = topk_routing(router, x2d, e, k)
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    slot_token = jnp.repeat(jnp.arange(g), k)

    if tokens_split:
        # per-chip buffers for ALL experts, then all_to_all expert-major
        capacity = min(int(g * k / e * cfg.capacity_factor) + 1, g)
        order, dest, keep = _dispatch_tables(flat_ids, e, capacity)
        xin = x2d[slot_token[order]]
        buf = jnp.zeros((e * capacity + 1, d), x_loc.dtype).at[dest].set(xin)
        buf = buf[:-1].reshape(e, capacity, d)
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)          # (E_loc, M*C, d)
        out = _expert_ffn(buf, experts, x_loc.dtype)
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                 tiled=True)          # (E, C, d)
        flat_out = out.reshape(e * capacity, d)
        safe = jnp.clip(dest, 0, e * capacity - 1)
        slot_out = jnp.where(keep[:, None], flat_out[safe], 0.0)
        slot_out = slot_out * flat_gates[order][:, None].astype(x_loc.dtype)
        y2d = jnp.zeros((g, d), x_loc.dtype).at[slot_token[order]].add(slot_out)
    else:
        # tokens replicated across EP axis: keep only this chip's experts,
        # combine partial outputs with a psum
        capacity = min(int(g * k / e * cfg.capacity_factor) + 1, g)
        local = (flat_ids >= col * e_loc) & (flat_ids < (col + 1) * e_loc)
        rel_ids = jnp.where(local, flat_ids - col * e_loc, e_loc)
        order = jnp.argsort(rel_ids, stable=True)
        sorted_ids = rel_ids[order]
        starts = jnp.searchsorted(sorted_ids, jnp.arange(e_loc), side="left")
        pos = jnp.arange(rel_ids.shape[0]) - starts[jnp.clip(sorted_ids, 0, e_loc - 1)]
        keep = (sorted_ids < e_loc) & (pos < capacity)
        dest = jnp.where(keep, sorted_ids * capacity + pos, e_loc * capacity)
        xin = x2d[slot_token[order]]
        buf = jnp.zeros((e_loc * capacity + 1, d), x_loc.dtype).at[dest].set(xin)
        out = _expert_ffn(buf[:-1].reshape(e_loc, capacity, d), experts,
                          x_loc.dtype)
        flat_out = out.reshape(e_loc * capacity, d)
        safe = jnp.clip(dest, 0, e_loc * capacity - 1)
        slot_out = jnp.where(keep[:, None], flat_out[safe], 0.0)
        slot_out = slot_out * flat_gates[order][:, None].astype(x_loc.dtype)
        y2d = jnp.zeros((g, d), x_loc.dtype).at[slot_token[order]].add(slot_out)
        y2d = jax.lax.psum(y2d, axis)
    return y2d.reshape(b, s, d)


def _route_ep(p, x: jnp.ndarray, cfg, ctx) -> jnp.ndarray:
    import numpy as np
    mesh = ctx["mesh"]
    axis = ctx.get("ep_axis") or ctx["model"]
    sizes = dict(mesh.shape)
    m = sizes[axis]
    bax = ctx["batch"]
    b, s, d = x.shape
    # longest batch-axis prefix that divides B (long-context has B=1)
    use = list(bax)
    while use and b % int(np.prod([sizes[a] for a in use])):
        use.pop()
    bspec = tuple(use) if use else None
    batch_covers_ep = bspec is not None and axis in bspec
    split_seq = (not batch_covers_ep and ctx["seq_shard"]
                 and s % m == 0 and s >= m)
    # tokens distributed across the EP axis -> all_to_all regroup;
    # tokens replicated across it -> local experts + psum combine
    tokens_split = batch_covers_ep or split_seq
    x_spec = P(bspec, axis if split_seq else None, None)
    e_spec = P(axis, None, None)
    body = functools.partial(_route_ep_body, cfg=cfg, axis=axis,
                             tokens_split=tokens_split)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), e_spec, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(p["router"], p["experts"], x)


def moe_apply(p, x: jnp.ndarray, cfg, quant: bool = False) -> jnp.ndarray:
    """p: router (d, E); experts {'gate','up','down'} stacked (E, ...);
    optional 'shared' swiglu params.  x: (B, S, d)."""
    b, s, d = x.shape
    ctx = shctx.current()
    ep_ax = (ctx or {}).get("ep_axis") or (ctx or {}).get("model")
    use_ep = (ctx is not None and ctx.get("mesh") is not None
              and ep_ax is not None
              and cfg.n_experts % dict(ctx["mesh"].shape)[ep_ax] == 0)
    if use_ep:
        y2d = _route_ep(p, x, cfg, ctx).reshape(b * s, d)
    else:
        y2d = _route_local(p, x.reshape(b * s, d), cfg)

    if "shared" in p:
        y2d = y2d + swiglu(p["shared"], x.reshape(b * s, d), quant=quant)
    return y2d.reshape(b, s, d)
