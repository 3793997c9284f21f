"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For configurations whose per-stage footprint exceeds HBM even after TP+FSDP
(e.g. >16 GB/chip at small meshes) the launcher can add a ``pipe`` mesh axis.
Stages hold disjoint layer groups; microbatches stream through with the
classic (n_micro + n_stages - 1)-slot schedule.

``pipeline_forward`` is the building block (forward pass), validated against
sequential execution in tests/test_distributed.py on 8 host devices.  For
training, the same schedule applies to the VJP (run the pipeline over the
cotangent stream in reverse) — wired through ``jax.linear_transpose`` is out
of scope for the default 512-chip DP x TP dry-run mesh (see DESIGN.md).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _stage_step(stage_fn, stage_params, x):
    return stage_fn(stage_params, x)


def pipeline_forward(stage_fn: Callable, params_stacked, x_micro,
                     *, axis: str = "pipe"):
    """Run inside shard_map over ``axis``.

    ``params_stacked``: per-stage params (leading dim sharded over ``axis``
    outside; inside, each stage sees its own slice with leading dim 1).
    ``x_micro``: (n_micro, mb, ...) — meaningful on stage 0.
    Returns (n_micro, mb, ...) outputs — meaningful on the last stage.
    """
    n_stages = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    total_ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    my_params = jax.tree.map(lambda p: p[0], params_stacked)
    carry = jnp.zeros_like(x_micro[0])
    outputs = jnp.zeros_like(x_micro)

    for t in range(total_ticks):                       # static schedule
        inject = x_micro[jnp.minimum(t, n_micro - 1)]
        cur = jnp.where(stage == 0, inject, carry)
        y = _stage_step(stage_fn, my_params, cur)
        # last stage emits micro t - (n_stages - 1)
        out_idx = t - (n_stages - 1)
        valid = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_idx, 0), 0),
            lambda o: o, outputs)
        carry = jax.lax.ppermute(y, axis, perm)
    # only the last stage wrote anything; psum makes the result replicated
    # so out_specs=P() is well-defined on every shard
    return jax.lax.psum(outputs, axis)


def make_pipelined_fn(mesh: Mesh, stage_fn: Callable, *, axis: str = "pipe"):
    """Wrap ``pipeline_forward`` in shard_map on ``mesh`` (params stacked on
    the stage axis; activations enter on stage 0 and leave on the last)."""
    fn = functools.partial(pipeline_forward, stage_fn, axis=axis)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
