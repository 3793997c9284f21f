"""Static introspection of Pallas kernel instantiations — no execution.

PR 7's program auditor proves properties of every serve program's jaxpr and
HLO but goes blind at the ``pallas_call`` boundary: BlockSpec index maps
are plain Python functions XLA never sees, and exactly those functions
decide the data movement the paper's claims are counted in (a
scalar-prefetched page-table index map that dereferences out of bounds is
*silent garbage* on TPU — the same failure class as the PR 3 CPU-SPMD
miscompiles).  This module is the machinery that makes the boundary
auditable:

* :class:`KernelInstantiation` — one concrete (grid, BlockSpecs, operand
  shapes/dtypes, scratch, scalar-prefetch values) tuple, built by each
  kernel's ``audit_specs()`` hook from the SAME spec-builder the shipped
  ``pallas_call`` uses, so the audited index maps are the shipped ones.
* :func:`check_bounds` — evaluates every index map over the full grid
  (grids are small and static: an exhaustive sweep IS a proof) and checks
  every block index lands inside its operand.
* :func:`vmem_footprint` — per-instantiation VMEM bytes: double-buffered
  in/out block buffers plus scratch, the number gated against
  ``benchmarks/baselines/kernel_audit.json``.
* :func:`block_traffic` — bytes moved per invocation from BlockSpecs x
  grid x dtype, with the pipeline's revisit elision (a block whose index
  does not change between consecutive grid steps is not re-fetched) and
  per-kernel refinement hooks (plane skipping, masked-dead blocks).
* :func:`extract_pallas_calls` — the jaxpr-side census: every
  ``pallas_call`` eqn in a traced serve program, with enclosing-scan trip
  counts multiplied through, so per-invocation statics compose into
  per-tick byte tables (the cost model ``simulator/`` consumes).

The rule families consuming this live in ``analysis.kernel_rules``.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

# Pallas pipelines double-buffer the in/out block windows (fetch block j+1
# while computing on block j); scratch is single-buffered, it never streams.
DOUBLE_BUFFER = 2


def _np_dtype(dt) -> np.dtype:
    """np.dtype for numpy/jnp dtypes AND jnp scalar types (bf16 included)."""
    return np.dtype(getattr(dt, "dtype", dt))


def dtype_bytes(dt) -> int:
    return _np_dtype(dt).itemsize


@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """One operand's BlockSpec view: the shipped block shape + index map."""

    name: str
    shape: Tuple[int, ...]
    dtype: str
    block_shape: Tuple[int, ...]
    index_map: Callable

    @property
    def block_bytes(self) -> int:
        return int(np.prod(self.block_shape)) * dtype_bytes(self.dtype)

    @property
    def n_blocks(self) -> Tuple[int, ...]:
        """Blocks per dim: pallas requires block index ``b`` to satisfy
        ``0 <= b < ceil(extent / block)`` — anything else reads memory the
        operand does not own."""
        return tuple(-(-s // b) for s, b in zip(self.shape, self.block_shape))


@dataclasses.dataclass
class KernelInstantiation:
    """One concrete kernel configuration the verifier can sweep.

    ``scalars`` are the scalar-prefetch operand VALUES (page tables, skip
    tables, lengths) — small integer metadata, exactly the data that
    *decides* movement; evaluating index maps over them touches no tensor
    data and executes no kernel.  ``meta`` carries kernel-family facts the
    rule families interpret (page_len, lengths, trash page, exponents...).
    """

    kernel: str  # family: "paged_attention" | "bitplane_matmul" | "log2quant"
    case: str  # geometry id, e.g. "ragged512.s1"
    grid: Tuple[int, ...]
    inputs: Tuple[OperandSpec, ...]
    outputs: Tuple[OperandSpec, ...]
    scratch: Tuple[Tuple[Tuple[int, ...], str], ...] = ()
    scalars: Tuple[np.ndarray, ...] = ()
    meta: Dict = dataclasses.field(default_factory=dict)

    @property
    def name(self) -> str:
        return f"{self.kernel}/{self.case}"

    @property
    def operands(self) -> Tuple[OperandSpec, ...]:
        return self.inputs + self.outputs

    @property
    def grid_points(self) -> int:
        return int(np.prod(self.grid))


def make_operand(name: str, shape, dtype, block_spec) -> OperandSpec:
    """OperandSpec from a ``pl.BlockSpec`` — the object handed to
    ``pallas_call``, so audit and kernel share one index map."""
    return OperandSpec(
        name=name,
        shape=tuple(int(s) for s in shape),
        dtype=_np_dtype(dtype).name,
        block_shape=tuple(int(b) for b in block_spec.block_shape),
        index_map=block_spec.index_map,
    )


def scratch_entry(ref) -> Tuple[Tuple[int, ...], str]:
    """(shape, dtype name) from a ``pltpu.VMEM(...)`` MemoryRef."""
    return tuple(int(s) for s in ref.shape), _np_dtype(ref.dtype).name


def iter_grid(grid: Sequence[int]) -> Iterator[Tuple[int, ...]]:
    """Row-major sweep, last dim innermost — the TPU grid execution order
    (and the order pallas's revisit elision is defined over)."""
    return itertools.product(*(range(int(g)) for g in grid))


def eval_index_map(op: OperandSpec, gidx: Tuple[int, ...], scalars: Sequence[np.ndarray]):
    """Block indices the shipped index map produces for one grid point.

    Scalar-prefetch refs are passed as the real numpy arrays — ``tab[bi,
    j]`` works identically on a Ref and an ndarray.  Returns a tuple of
    ints, or raises whatever the index map raises (an out-of-range table
    read raises ``IndexError`` here instead of fetching garbage on TPU —
    the verifier treats both as bounds violations).
    """
    out = op.index_map(*gidx, *scalars)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(i) for i in out)


# ---------------------------------------------------------------------------
# bounds proofs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BoundsViolation:
    operand: str
    gidx: Tuple[int, ...]
    detail: str


def check_bounds(inst: KernelInstantiation) -> List[BoundsViolation]:
    """Exhaustively prove every block dereference in-bounds.

    Grids are small and static (a few hundred points across the whole
    audit matrix), so enumeration is a proof, not a sample.  A dimension's
    block index must satisfy ``0 <= b < ceil(extent / block)``; an index
    map that *raises* (numpy catches the out-of-range scalar read that TPU
    hardware would silently satisfy with garbage) is reported the same way.
    """
    out: List[BoundsViolation] = []
    for op in inst.operands:
        nb = op.n_blocks
        for gidx in iter_grid(inst.grid):
            try:
                bidx = eval_index_map(op, gidx, inst.scalars)
            except Exception as e:  # noqa: BLE001 — any raise is a violation
                out.append(
                    BoundsViolation(op.name, gidx, f"index map raised {type(e).__name__}: {e}")
                )
                continue
            if len(bidx) != len(op.block_shape):
                out.append(
                    BoundsViolation(
                        op.name,
                        gidx,
                        f"index map arity {len(bidx)} != block rank {len(op.block_shape)}",
                    )
                )
                continue
            for d, (b, n) in enumerate(zip(bidx, nb)):
                if not 0 <= b < n:
                    out.append(
                        BoundsViolation(
                            op.name,
                            gidx,
                            f"block index {b} outside [0, {n}) on dim {d} "
                            f"(operand {op.shape}, block {op.block_shape})",
                        )
                    )
    return out


# ---------------------------------------------------------------------------
# VMEM footprint
# ---------------------------------------------------------------------------


def vmem_footprint(inst: KernelInstantiation) -> Dict:
    """Resident VMEM bytes: 2x (double-buffered) in/out block windows plus
    single-buffered scratch.  ``n_buffers`` is structural (exact gate);
    ``vmem_bytes`` is gated at 10% rtol in ``kernel_rules``."""
    buffers: Dict[str, int] = {}
    for op in inst.operands:
        buffers[op.name] = DOUBLE_BUFFER * op.block_bytes
    for i, (shape, dtype) in enumerate(inst.scratch):
        buffers[f"scratch{i}"] = int(np.prod(shape)) * dtype_bytes(dtype)
    return {
        "n_buffers": len(buffers),
        "vmem_bytes": int(sum(buffers.values())),
        "buffers": buffers,
    }


# ---------------------------------------------------------------------------
# static byte-traffic model
# ---------------------------------------------------------------------------


def block_traffic(
    inst: KernelInstantiation,
    live: Optional[Callable[[str, Tuple[int, ...]], bool]] = None,
    refine_bytes: Optional[Callable[[str, Tuple[int, ...], float], float]] = None,
) -> Dict:
    """Bytes moved per invocation, derived from BlockSpecs x grid x dtype.

    Semantics:

    * **revisit elision** — pallas does not re-fetch a block whose index is
      unchanged from the previous grid step (the same contract that makes
      accumulator outputs work); consecutive identical indices count once.
    * ``live(name, gidx) -> bool`` — kernel-family hook: a block that is
      fully masked out of the result (every position past ``length``, for
      the paged kernel) moves no *useful* bytes and is excluded, mirroring
      the runtime counters (``ops.gather_traffic_counts`` counts only pages
      holding valid tokens).
    * ``refine_bytes(name, gidx, nominal) -> float`` — intra-block
      refinement: the bit-plane kernel's ``@pl.when`` plane skip fetches
      ``bits - min_plane`` of the 8 plane slabs of each block.

    Returns ``{"read": {name: bytes}, "written": {...}, "fetches": {name:
    count}}`` — fetches are post-elision, post-masking block counts.
    """
    read: Dict[str, float] = {op.name: 0.0 for op in inst.inputs}
    written: Dict[str, float] = {op.name: 0.0 for op in inst.outputs}
    fetches: Dict[str, int] = {op.name: 0 for op in inst.operands}
    prev: Dict[str, object] = {op.name: None for op in inst.operands}

    for gidx in iter_grid(inst.grid):
        for op in inst.inputs:
            bidx = eval_index_map(op, gidx, inst.scalars)
            if bidx == prev[op.name]:
                continue
            prev[op.name] = bidx
            if live is not None and not live(op.name, gidx):
                continue
            nominal = float(op.block_bytes)
            if refine_bytes is not None:
                nominal = refine_bytes(op.name, gidx, nominal)
            read[op.name] += nominal
            fetches[op.name] += 1
        for op in inst.outputs:
            bidx = eval_index_map(op, gidx, inst.scalars)
            if bidx == prev[op.name]:
                continue
            prev[op.name] = bidx
            written[op.name] += float(op.block_bytes)
            fetches[op.name] += 1
    return {"read": read, "written": written, "fetches": fetches}


# ---------------------------------------------------------------------------
# jaxpr-side census: pallas_call sites inside traced serve programs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PallasCallSite:
    """One ``pallas_call`` eqn in a traced program, loop-scaled."""

    kernel_name: str  # the kernel body's function name, e.g. "_paged_attn_kernel"
    multiplier: int  # product of enclosing scan trip counts
    grid: Tuple[int, ...]
    operand_shapes: Tuple[Tuple[int, ...], ...]
    operand_dtypes: Tuple[str, ...]
    block_shapes: Tuple[Tuple[int, ...], ...]

    @property
    def operand_bytes(self) -> int:
        """Bytes of every operand the call streams once (the dense upper
        bound; savings fractions come from the matching audit_specs case)."""
        return int(
            sum(
                int(np.prod(s)) * dtype_bytes(d)
                for s, d in zip(self.operand_shapes, self.operand_dtypes)
            )
        )


def _jaxprs_of(v):
    from jax.extend import core

    if isinstance(v, core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_of(x)


def extract_pallas_calls(jaxpr, _mult: int = 1) -> List[PallasCallSite]:
    """Every ``pallas_call`` eqn in ``jaxpr`` and all sub-jaxprs, with
    enclosing ``scan`` trip counts multiplied through (``while`` bodies are
    scaled x1 — trip counts are data-dependent; the serve tick's loops are
    fixed-length scans, so the census is exact where it matters)."""
    from jax.extend import core

    jaxpr = jaxpr.jaxpr if isinstance(jaxpr, core.ClosedJaxpr) else jaxpr
    out: List[PallasCallSite] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            # the kernel body's function name, from the kernel jaxpr's
            # debug info ("<name> at <file>:<line>")
            src = eqn.params["jaxpr"].debug_info.func_src_info
            name = src.split(" ")[0]
            # an operand passed more than once (the paged kernel's pool,
            # once per page of a block) is one buffer: count it once
            invars = list({id(v): v for v in eqn.invars}.values())
            avals = [v.aval for v in invars]
            out.append(
                PallasCallSite(
                    kernel_name=name,
                    multiplier=_mult,
                    grid=tuple(int(g) for g in gm.grid),
                    operand_shapes=tuple(tuple(int(s) for s in a.shape) for a in avals),
                    operand_dtypes=tuple(_np_dtype(a.dtype).name for a in avals),
                    block_shapes=tuple(
                        tuple(int(getattr(b, "block_size", b)) for b in bm.block_shape)
                        for bm in gm.block_mappings
                    ),
                )
            )
            continue
        sub_mult = _mult
        if eqn.primitive.name == "scan":
            sub_mult = _mult * int(eqn.params.get("length", 1))
        for sub in eqn.params.values():
            for j in _jaxprs_of(sub):
                out.extend(extract_pallas_calls(j, sub_mult))
    return out
