"""Jaxpr rule family: structural invariants of the traced serve programs.

These rules run on ``jax.make_jaxpr`` output — pure tracing, no lowering,
no execution — and encode invariants that runtime parity tests can only
check *after* a regression ships (DESIGN.md §Program audit):

* :func:`rule_no_dense_pool_gather` — with ``attn_kernel=True`` the whole
  point of the Pallas paged-attention kernel (PR 6) is that the dense
  ``pool[table]`` gather never materializes; a ``gather`` reading the KV
  page pool inside a kernel-enabled tick program means the dispatch
  silently fell back to the dense path.
* :func:`rule_no_host_callback` — a host callback (``debug_callback`` left
  behind from debugging, ``pure_callback``/``io_callback``, infeed/outfeed)
  inside a tick program forces a device->host sync every tick and breaks
  the "one jitted program per tick" contract from PR 1.
* :func:`rule_no_double_precision` / :func:`rule_no_integer_upcast` — the
  shift-add path is integer (int32 planes/accumulators) by design (PAPER
  §IV); an f64/c128 value anywhere in a tick program, or an i64/u64 value
  in a quant program, is a silent upcast that doubles traffic on exactly
  the path whose claim is *fewer* bytes touched.

Every helper works on ``Jaxpr`` or ``ClosedJaxpr`` and recurses into every
sub-jaxpr (pjit / scan / while / cond / custom calls), so rules see through
the jitted wrappers and the tick's ``lax.scan``.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import numpy as np
from jax.extend import core

from repro.analysis.report import Finding

# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _as_jaxpr(j) -> core.Jaxpr:
    return j.jaxpr if isinstance(j, core.ClosedJaxpr) else j


def _jaxprs_in(v) -> Iterator[core.Jaxpr]:
    if isinstance(v, core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_in(x)


def sub_jaxprs(eqn) -> Iterator[core.Jaxpr]:
    """Every jaxpr referenced by an eqn's params (pjit ``jaxpr``, scan
    ``jaxpr``, while ``cond_jaxpr``/``body_jaxpr``, cond ``branches``...)."""
    for v in eqn.params.values():
        yield from _jaxprs_in(v)


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every eqn in the jaxpr and all nested sub-jaxprs."""
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield eqn
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

CALLBACK_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "outside_call",
        "host_callback_call",
        "infeed",
        "outfeed",
    }
)


def rule_no_host_callback(jaxpr, variant: str, program: str) -> List[Finding]:
    """Tick programs must be host-silent: no callback / infeed / outfeed
    primitive anywhere in the traced program."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS or "callback" in name:
            out.append(
                Finding(
                    rule="no-host-callback",
                    variant=variant,
                    program=program,
                    detail=f"host-syncing primitive {name!r} in the program",
                )
            )
    return out


_WIDE_FLOAT = ("float64", "complex128")
_WIDE_INT = ("int64", "uint64")


def rule_no_double_precision(jaxpr, variant: str, program: str) -> List[Finding]:
    """No f64/c128 value may appear anywhere in a serve program — CPU smoke
    silently tolerates them; accelerators pay double bandwidth (or trap)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for aval in _avals(eqn):
            if str(aval.dtype) in _WIDE_FLOAT:
                out.append(
                    Finding(
                        rule="no-double-precision",
                        variant=variant,
                        program=program,
                        detail=(
                            f"{aval.dtype} value of shape "
                            f"{tuple(aval.shape)} at primitive "
                            f"{eqn.primitive.name!r}"
                        ),
                    )
                )
                break  # one finding per eqn is enough
    return out


def rule_no_integer_upcast(jaxpr, variant: str, program: str) -> List[Finding]:
    """Quant programs: the shift-add path accumulates in int32 (PAPER §IV)
    — an i64/u64 value means numpy-int leakage or an XLA promotion widened
    the integer path, silently doubling plane-traffic bytes."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for aval in _avals(eqn):
            if str(aval.dtype) in _WIDE_INT:
                out.append(
                    Finding(
                        rule="no-integer-upcast",
                        variant=variant,
                        program=program,
                        detail=(
                            f"{aval.dtype} value of shape "
                            f"{tuple(aval.shape)} at primitive "
                            f"{eqn.primitive.name!r}"
                        ),
                    )
                )
                break
    return out


def rule_no_dense_pool_gather(jaxpr, variant: str, program: str, *, n_pages: int) -> List[Finding]:
    """Kernel-enabled tick programs must never gather the KV page pool.

    The dense fallback is ``pool[table]`` (``models.attention._paged_gather``)
    — a ``gather`` whose operand is a *floating* array carrying the pool's
    page axis (``n_pages``).  Page-table index arithmetic (int32 gathers)
    passes; any float gather off the pool is the exact dense read the PR 6
    kernel exists to eliminate.  ``n_pages`` should be sized distinctively
    by the caller (``analysis.programs`` picks a value no other dimension
    uses) so the page axis is unambiguous."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        if not np.issubdtype(np.dtype(aval.dtype), np.floating):
            continue
        if n_pages in tuple(aval.shape):
            gathered = getattr(eqn.outvars[0], "aval", None)
            out.append(
                Finding(
                    rule="no-dense-pool-gather",
                    variant=variant,
                    program=program,
                    detail=(
                        f"float gather reads the page pool: operand "
                        f"{tuple(aval.shape)} ({aval.dtype}) -> "
                        f"{tuple(gathered.shape) if gathered is not None else '?'}"
                        f" — dense pool[table] fallback while the paged-"
                        f"attention kernel is enabled"
                    ),
                )
            )
    return out


def make_program_jaxpr(fn, args) -> core.ClosedJaxpr:
    """Trace ``fn`` (a scheduler program: plain jit OR an
    ``engine.jit_sharded`` wrapper) to a jaxpr without executing it.

    Sharded wrappers expose ``trace_context`` (the mesh + ``mesh_axes``
    binding their calls enter) and ``jitted``; plain jits trace directly.
    """
    import contextlib

    ctx = getattr(fn, "trace_context", None)
    target = getattr(fn, "jitted", fn)
    with ctx() if ctx is not None else contextlib.nullcontext():
        return jax.make_jaxpr(target)(*args)
