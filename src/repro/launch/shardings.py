"""Parameter/cache/batch partition rules with divisibility fallback.

TP rule per leaf (by pytree path name), FSDP rule on top (largest remaining
dim sharded on the data axis for leaves above a size threshold), and every
rule checks divisibility against the mesh — non-divisible dims stay
replicated (e.g. smollm's 9 heads under 16-way TP).
"""

from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaf-name -> index of the dim to shard on the model axis (negative = from
# the right; None = replicate).  Stacked layer leaves carry a leading repeat
# dim, hence counting from the right.
_TP_RULES = [
    # QeiHaN bit-plane weights (R, 8, K[, /8], N): same relative dims as
    # their float counterparts (packing only shrinks K)
    (r"\['(wq_q|wk_q|wv_q|gate_q|up_q|in_proj_q)'\]\.planes$", -1),
    (r"\['(wo_q|down_q|out_proj_q)'\]\.planes$", -2),
    (r"\.(w_scale|act_scale)$", None),
    (r"\['embed'\]$", 0),              # (V, d): vocab
    (r"\['lm_head'\]$", -1),           # (d, V): vocab
    (r"\['img_proj'\]$", -1),
    (r"\['(wq|wk|wv)'\]$", -1),        # (R, d, H*hd): heads
    (r"\['(bq|bk|bv)'\]$", -1),
    (r"\['wo'\]$", -2),                # (R, H*hd, d): input/head dim
    (r"\['experts'\]\['(gate|up|down)'\]$", -3),   # (R, E, ..): experts (EP)
    (r"\['(gate|up)'\]$", -1),         # (R, d, ff): ff
    (r"\['down'\]$", -2),              # (R, ff, d): ff
    (r"\['router'\]$", None),
    (r"\['in_proj'\]$", -1),           # (R, d, Z): inner (legacy fused)
    (r"\['(wz|wx)'\]$", -1),           # (R, d, d_inner)
    (r"\['(wb|wc|wdt)'\]$", None),     # small B/C/dt heads: replicate
    (r"\['out_proj'\]$", -2),          # (R, d_inner, d)
    (r"\['conv_w(x)?'\]$", -1),
    (r"\['conv_b(x)?'\]$", -1),
    (r"\['conv_[wb][bc]'\]$", None),
    (r"\['(dt_bias|a_log|d_skip)'\]$", -1),
    (r"\['norm'\]$", -1),              # (R, d_inner): gated-norm weight
    (r"\['(ln1|ln2|q_norm|k_norm|final_norm)'\]$", None),
]


def _axis_size(mesh: Mesh, axis: Optional[str]) -> int:
    if axis is None:
        return 1
    return mesh.shape[axis]


def _tp_dim(path: str, ndim: int) -> Optional[int]:
    if ndim == 0:                       # scalar placeholder (dropped weight)
        return None
    for pat, dim in _TP_RULES:
        if re.search(pat, path):
            if dim is None:
                return None
            return dim % ndim
    return None


_EXPERT_RE = re.compile(r"\['experts'\]\['(gate|up|down)'\]$")


def param_spec(path: str, shape: tuple, mesh: Mesh, *,
               model_axis: Optional[str] = "model",
               fsdp_axes: tuple = (),
               fsdp_threshold: int = 1 << 20,
               tp_scope: str = "all") -> P:
    ndim = len(shape)
    entries: list = [None] * ndim
    is_expert = bool(_EXPERT_RE.search(path))
    use_tp = model_axis is not None and (tp_scope == "all" or is_expert)
    msize = _axis_size(mesh, model_axis) if use_tp else 1
    if use_tp and msize > 1:
        tp = _tp_dim(path, ndim)
        if tp is not None and shape[tp] % msize == 0:
            entries[tp] = model_axis
    # FSDP: shard the largest remaining divisible dim on the given axes.
    # Expert weights under EP stay resident (shard_map owns them 1:1).
    if fsdp_axes and not (is_expert and tp_scope == "experts") \
            and int(np.prod(shape)) >= fsdp_threshold:
        fsize = int(np.prod([_axis_size(mesh, a) for a in fsdp_axes]))
        cands = sorted(range(ndim), key=lambda d: -shape[d])
        for d in cands:
            if entries[d] is None and shape[d] % fsize == 0:
                entries[d] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
                break
    return P(*entries)


def params_shardings(mesh: Mesh, params_tree: Any, *, fsdp: bool = True,
                     model_axis: Optional[str] = "model",
                     fsdp_axes: Optional[tuple] = None,
                     fsdp_threshold: int = 1 << 20,
                     tp_scope: str = "all",
                     ep_axis: Optional[str] = None) -> Any:
    from repro.launch.mesh import batch_axes
    if fsdp_axes is None:
        fax = batch_axes(mesh) if fsdp else ()
    else:
        fax = fsdp_axes if fsdp else ()
    # under EP-only scope, experts bind the EP axis
    eff_model = model_axis if model_axis is not None else ep_axis
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
    out = []
    for path, leaf in flat:
        spec = param_spec(jax.tree_util.keystr(path), leaf.shape, mesh,
                          model_axis=eff_model,
                          fsdp_axes=fax, fsdp_threshold=fsdp_threshold,
                          tp_scope=tp_scope)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_params_sharded(key, cfg, mesh: Mesh, *,
                        model_axis: Optional[str] = "model") -> Any:
    """Random weights built directly in their serving shardings (TP rules,
    no FSDP): ``init_params`` jitted with ``out_shardings`` from its
    ``eval_shape``, so no device ever holds an unsharded copy — a model
    larger than one chip is built on a mesh that holds it."""
    from repro.models.model import init_params

    def init(k):
        return init_params(k, cfg)

    shapes = jax.eval_shape(init, key)
    psh = params_shardings(mesh, shapes, fsdp=False, model_axis=model_axis)
    return jax.jit(init, out_shardings=psh)(key)


def opt_shardings(mesh: Mesh, opt_tree: Any, param_shardings_tree: Any,
                  extra_axes: tuple = ()) -> Any:
    """Moments follow their parameters; scalars replicate.

    ``extra_axes``: additionally shard each moment's largest free dim on
    these axes — f32 m/v are the optimizer-memory hog, and since the update
    is elementwise any sharding is valid (EP expert weights keep their
    weights resident but spread their moments)."""
    rep = NamedSharding(mesh, P())

    def widen(sh, leaf):
        if not extra_axes:
            return sh
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        used = set()
        for e in spec:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a:
                    used.add(a)
        free = tuple(a for a in extra_axes if a not in used)
        if not free:
            return sh
        fsize = int(np.prod([_axis_size(mesh, a) for a in free]))
        for d in sorted(range(len(leaf.shape)), key=lambda i: -leaf.shape[i]):
            if spec[d] is None and leaf.shape[d] % fsize == 0:
                spec[d] = free if len(free) > 1 else free[0]
                break
        return NamedSharding(mesh, P(*spec))

    m_sh = jax.tree.map(widen, param_shardings_tree, opt_tree["m"])
    return {
        "m": m_sh,
        "v": jax.tree.map(lambda s: s, m_sh),
        "step": rep,
    }


def batch_shardings(mesh: Mesh, batch_tree: Any,
                    axes: Optional[tuple] = None) -> Any:
    from repro.launch.mesh import batch_axes
    bax = tuple(axes) if axes is not None else batch_axes(mesh)

    def one(leaf):
        b = leaf.shape[0]
        # use the longest prefix of batch axes that divides the batch
        use = list(bax)
        while use and b % int(np.prod([mesh.shape[a] for a in use])):
            use.pop()
        if use:
            return NamedSharding(mesh, P(tuple(use)))
        return NamedSharding(mesh, P())
    return jax.tree.map(one, batch_tree)


def cache_shardings(mesh: Mesh, caches_tree: Any, *, batch: int,
                    long_context: bool = False,
                    axes: Optional[tuple] = None,
                    model_axis: Optional[str] = "model",
                    ssm_model: bool = True,
                    paged: bool = False) -> Any:
    """KV caches (R, B, S, Hkv, D) / SSM states (R, B, H, P, N).

    decode: batch on the data axes; long-context (batch=1): KV sequence dim
    on data instead.  Model-axis sharding: kv-heads / ssm-heads when
    divisible.  The per-slot ``length`` vector (``init_caches(per_slot=
    True)``, shape (B,)) follows the batch axes like every other per-row
    cache leaf — the scalar whole-batch ``length`` replicates.

    ``ssm_model=False`` keeps the SSM/conv state leaves batch-only: a
    model-sharded recurrent state carried through the serve tick's scan is
    miscompiled by the jax 0.4.37 CPU SPMD pipeline (partially-replicated
    meshes; tests/test_serve_sharded.py), so the *executing* serve path
    (``serve_shardings``) opts out while lowering-only consumers (the
    dry-run) keep the full TP image.

    ``paged=True`` reads the KV leaves as page pools (R, P, page_len, Hkv,
    D): the page dim takes the batch role (pages on data — every slot's
    rows live in its pages, scattered/gathered through the page table) and
    the in-page token dim is NEVER sharded, so the (page, offset) indexing
    of ``models.attention._paged_write`` touches no sharded-axis reshape.
    """
    from repro.launch.mesh import batch_axes
    bax = tuple(axes) if axes is not None else batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in bax]))
    msz = (mesh.shape[model_axis]
           if model_axis and model_axis in mesh.axis_names else 1)

    def one_path(path, leaf):
        name = jax.tree_util.keystr(path)
        shape = leaf.shape
        if name.endswith("['length']"):
            if len(shape) == 1 and nb > 1 and shape[0] % nb == 0:
                return NamedSharding(mesh, P(bax if len(bax) > 1 else bax[0]))
            return NamedSharding(mesh, P())
        entries = [None] * len(shape)
        if paged and re.search(r"'[kv]_(codes|scale)'", name):
            # log2-quantized page pool: codes (R, P, page_len, Hkv, D) and
            # per-page scales (R, P, Hkv) — pages on data, like the dense
            # pool; a page's codes and its scale land on the same shard so
            # dequant (codes + scale -> rows) stays local
            if shape[1] % nb == 0 and nb > 1:
                entries[1] = bax if len(bax) > 1 else bax[0]
            return NamedSharding(mesh, P(*entries))
        if paged and re.search(r"'[kv]_tail'", name):
            # f32 tail ring (R, B, 2*page_len+1, Hkv, D): per-slot rows,
            # batch on data like every other per-row cache leaf
            if shape[1] % nb == 0 and nb > 1:
                entries[1] = bax if len(bax) > 1 else bax[0]
            return NamedSharding(mesh, P(*entries))
        if paged and ("'k'" in name or "'v'" in name):
            # page pool (R, P, page_len, Hkv, D): pages on data only
            if shape[1] % nb == 0 and nb > 1:
                entries[1] = bax if len(bax) > 1 else bax[0]
            return NamedSharding(mesh, P(*entries))
        if "'k'" in name or "'v'" in name:          # (R, B, S, Hkv, D)
            if long_context:
                if shape[2] % nb == 0 and nb > 1:
                    entries[2] = bax if len(bax) > 1 else bax[0]
            else:
                if shape[1] % nb == 0 and nb > 1:
                    entries[1] = bax if len(bax) > 1 else bax[0]
                # kv heads rarely divide the TP axis; the seq dim always does
                if msz > 1 and shape[2] % msz == 0:
                    entries[2] = model_axis
        elif "'ssm'" in name:                       # (R, B, H, P, N)
            if shape[1] % nb == 0 and nb > 1:
                entries[1] = bax if len(bax) > 1 else bax[0]
            if ssm_model and msz > 1 and shape[2] % msz == 0:
                entries[2] = model_axis
        elif "'conv'" in name:                      # (R, B, W-1, C)
            if shape[1] % nb == 0 and nb > 1:
                entries[1] = bax if len(bax) > 1 else bax[0]
            if ssm_model and msz > 1 and shape[3] % msz == 0:
                entries[3] = model_axis
        return NamedSharding(mesh, P(*entries))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_tree)
    return jax.tree_util.tree_unflatten(
        treedef, [one_path(p, l) for p, l in flat])


def split_kv_specs(mesh: Mesh, *, splits: int, batch: int,
                   model_axis: Optional[str] = "model",
                   axes: Optional[tuple] = None) -> dict:
    """Split-KV flash-decode partial-reduce rule (kernels/paged_attention).

    The paged-attention kernel partitions the KV page axis into ``splits``
    contiguous runs; each run emits partial online-softmax state — ``acc``
    (B, split, G, R, D) plus the (m, l) statistics (B, split, G, R) — and
    the cross-split merge (``ops.merge_split_softmax``) is the only
    reduction that crosses runs.  Under a mesh the split axis rides the
    model axis (each model shard owns its page run and reads nothing
    else), the batch axis rides data like every per-slot tensor, and the
    merge ships one (B, G, R)-sized triple per shard instead of
    all-gathering cache pages.

    Returns ``{"partial": P, "stat": P}`` — the jit-boundary image of the
    ``models.sharding`` ``"kvsplit"`` / ``"kvsplit_stat"`` hint kinds
    (same divisibility fallback: a non-divisible axis stays replicated).
    """
    from repro.launch.mesh import batch_axes
    bax = tuple(axes) if axes is not None else batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in bax]))
    msz = (mesh.shape[model_axis]
           if model_axis and model_axis in mesh.axis_names else 1)
    b_entry = (bax if len(bax) > 1 else bax[0]) \
        if nb > 1 and batch % nb == 0 else None
    s_entry = model_axis if msz > 1 and splits % msz == 0 else None
    return {
        "partial": P(b_entry, s_entry, None, None, None),
        "stat": P(b_entry, s_entry, None, None),
    }


def serve_shardings(mesh: Mesh, params_tree: Any, caches_tree: Any, *,
                    batch: int,
                    model_axis: Optional[str] = "model",
                    axes: Optional[tuple] = None,
                    paged: bool = False) -> dict:
    """Everything the mesh-native serving stack pins at jit boundaries.

    One bundle so ``serving/engine.py`` / ``serving/scheduler.py`` consume a
    single object instead of re-deriving rules leaf by leaf:

    * ``params``  — TP rules (``_TP_RULES``: float weights AND packed
      bit-planes — the plane leaves shard on the same relative dims as their
      float counterparts, the decode-time image of the paper's §IV-B
      vault-level parallelism), no FSDP (serving wants weights resident).
    * ``caches``  — KV/SSM slot pool: batch on ``data``, kv-seq on ``model``
      when divisible, per-slot (B,) ``length`` on ``data``.  SSM/conv state
      stays batch-only (``ssm_model=False`` — the executing CPU SPMD
      pipeline miscompiles a model-sharded recurrent carry; see
      ``cache_shardings``).
    * ``logits``  — (B, V) decode carry: batch on ``data``, vocab replicated
      (the greedy argmax stays a local per-row reduction).
    * ``tokens`` / ``active`` — per-slot (B, ...) arrays on ``data``.
      ``tokens`` is any per-slot token slab — the (B, 1) decode token AND
      the chunked-prefill (B, chunk_len) chunk slab (the slab's row lands
      on the device holding that slot's cache rows, so the chunk write
      stays local); ``active`` likewise covers every (B,) host-built flag
      vector (the decode-active mask and the chunked ``chunk_valid`` /
      ``fresh`` / ``finishing`` vectors).
    * ``replicated`` — the catch-all for host-supplied scalars.

    ``paged=True`` (the paged slot pool, ISSUE 5): KV leaves are page
    pools sharded pages-on-data (see ``cache_shardings``); the host-built
    page table rides the ``tokens`` sharding — its rows follow the slots.
    With the paged-attention kernel enabled (``attn_kernel=``, ISSUE 6)
    the in-tick split-KV partials follow :func:`split_kv_specs` via the
    ``models.sharding`` hint kinds — no extra jit-boundary entry needed.
    """
    from repro.launch.mesh import batch_axes
    bax = tuple(axes) if axes is not None else batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in bax]))
    row = (P(bax if len(bax) > 1 else bax[0])
           if nb > 1 and batch % nb == 0 else P())
    return {
        "params": params_shardings(mesh, params_tree, fsdp=False,
                                   model_axis=model_axis),
        "caches": cache_shardings(mesh, caches_tree, batch=batch,
                                  axes=bax, model_axis=model_axis,
                                  ssm_model=False, paged=paged),
        "logits": NamedSharding(mesh, P(*row, None)),
        "tokens": NamedSharding(mesh, P(*row, None)),
        "active": NamedSharding(mesh, row),
        "replicated": NamedSharding(mesh, P()),
    }
