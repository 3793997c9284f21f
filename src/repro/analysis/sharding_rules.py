"""Sharded-rearrange hazard detector (rule family 2).

PR 3 hit two real jax-0.4.37 CPU-SPMD miscompiles on partially-replicated
meshes; the one this rule encodes: **split / concatenate / reshape along an
axis that carries the ``model`` mesh axis returns garbage**.  The codebase's
discipline (DESIGN.md §Sharded serving) is to pin the rearranged axis
REPLICATED immediately before the rearrangement (``models.sharding.shard(...,
force=True)`` / ``replicate()``) — rope inputs, the mamba conv window, the
SSD channel split all do this.  Until now that discipline lived in comments
and runtime bit-equality tests; this rule machine-checks it at trace time.

Mechanics: walk the traced program's eqns tracking, per jaxpr variable, the
``PartitionSpec`` most recently *pinned* on it — seeded from explicit
``sharding_constraint`` eqns and from the jit boundary's ``in_shardings``
— propagated only through spec-preserving ops (convert / copy).  A
``concatenate`` / ``slice`` / ``split`` / ``reshape`` whose operand carries
the model axis on a dimension the op rearranges, with no replication pin in
between, is exactly the documented hazard and is flagged.  Tensors with no
adjacent pin are *untracked* (GSPMD may or may not shard them — the rule
stays quiet rather than guessing), which is also why the pin discipline
matters: a pin is both the fix and the auditor's evidence.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from jax.extend import core

from repro.analysis.jaxpr_rules import _as_jaxpr, sub_jaxprs
from repro.analysis.report import Finding

# ops that rearrange data along axes (the miscompile surface)
REARRANGE_PRIMS = ("concatenate", "slice", "split", "reshape")
# ops a pinned spec survives unchanged (same shape, same layout)
_TRANSPARENT_PRIMS = ("convert_element_type", "copy", "stop_gradient", "sharding_constraint")


def _lookup(pinned: Dict[object, Tuple[tuple, str]], v):
    """pinned.get guarded against ``core.Literal`` invars (unhashable)."""
    return pinned.get(v) if isinstance(v, core.Var) else None


def _spec_of(sharding) -> Optional[tuple]:
    """PartitionSpec entries of a NamedSharding (None for GSPMD/opaque)."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    return tuple(spec)


def _model_dims(spec: tuple, model_axis: str) -> List[int]:
    out = []
    for i, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if model_axis in [n for n in names if n is not None]:
            out.append(i)
    return out


def _rearranged_dims(eqn) -> List[int]:
    """Dims an eqn rearranges: concat dim, sliced dims, reshaped dims."""
    name = eqn.primitive.name
    if name == "concatenate":
        return [int(eqn.params["dimension"])]
    if name == "split":
        return [int(eqn.params["axis"])]
    if name == "slice":
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None:
            return []
        starts = eqn.params.get("start_indices", ())
        limits = eqn.params.get("limit_indices", ())
        return [
            i
            for i, (s, l, n) in enumerate(zip(starts, limits, aval.shape))
            if not (int(s) == 0 and int(l) == int(n))
        ]
    if name == "reshape":
        aval = getattr(eqn.invars[0], "aval", None)
        out = getattr(eqn.outvars[0], "aval", None)
        if aval is None or out is None:
            return []
        old, new = tuple(aval.shape), tuple(out.shape)
        # dims in the preserved common prefix/suffix are untouched; the
        # middle (merged/split) region is the rearranged part
        pre = 0
        while pre < len(old) and pre < len(new) and old[pre] == new[pre]:
            pre += 1
        suf = 0
        while suf < len(old) - pre and suf < len(new) - pre and old[-1 - suf] == new[-1 - suf]:
            suf += 1
        return list(range(pre, len(old) - suf))
    return []


def rule_sharded_rearrange(
    jaxpr, variant: str, program: str, *, model_axis: str = "model"
) -> List[Finding]:
    """Flag rearrange ops whose operand is pinned ``model``-sharded on a
    rearranged dim (see module docstring).  Works on ``Jaxpr`` /
    ``ClosedJaxpr``; recurses into every sub-jaxpr, seeding inner tracking
    from pjit ``in_shardings`` where present."""
    findings: List[Finding] = []

    def walk(j: core.Jaxpr, seed: Dict[object, Tuple[tuple, str]]) -> None:
        # var -> (spec entries, where the pin came from)
        pinned: Dict[object, Tuple[tuple, str]] = dict(seed)
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name == "sharding_constraint":
                spec = _spec_of(eqn.params.get("sharding"))
                if spec is not None:
                    pinned[eqn.outvars[0]] = (spec, "sharding_constraint")
                continue
            if name in REARRANGE_PRIMS:
                dims = _rearranged_dims(eqn)
                for v in eqn.invars:
                    entry = _lookup(pinned, v)
                    if entry is None:
                        continue
                    spec, src = entry
                    hot = sorted(set(dims) & set(_model_dims(spec, model_axis)))
                    if hot:
                        aval = getattr(v, "aval", None)
                        findings.append(
                            Finding(
                                rule="sharded-rearrange",
                                variant=variant,
                                program=program,
                                detail=(
                                    f"{name} rearranges dim(s) {hot} of a "
                                    f"tensor pinned {spec} (via {src}, "
                                    f"shape {tuple(aval.shape) if aval is not None else '?'}"
                                    f") — {model_axis}-sharded axis must be "
                                    f"pinned replicated before "
                                    f"split/concat/reshape (jax-0.4.37 "
                                    f"CPU-SPMD miscompile, DESIGN.md "
                                    f"§Sharded serving)"
                                ),
                            )
                        )
                # rearranged output loses the pin
            elif name in _TRANSPARENT_PRIMS:
                entry = _lookup(pinned, eqn.invars[0]) if eqn.invars else None
                if entry is not None and eqn.outvars:
                    pinned[eqn.outvars[0]] = entry

            # recurse with seeds mapped through the call boundary
            for sub in sub_jaxprs(eqn):
                inner_seed: Dict[object, Tuple[tuple, str]] = {}
                # positional: pjit/scan pass eqn.invars -> sub.invars
                # (best-effort — lengths differ for scan carries; zip stops)
                for outer_v, inner_v in zip(eqn.invars, sub.invars):
                    entry = _lookup(pinned, outer_v)
                    if entry is not None:
                        outer_aval = getattr(outer_v, "aval", None)
                        inner_aval = getattr(inner_v, "aval", None)
                        if (
                            outer_aval is not None
                            and inner_aval is not None
                            and tuple(getattr(outer_aval, "shape", ()))
                            == tuple(getattr(inner_aval, "shape", ()))
                        ):
                            inner_seed[inner_v] = entry
                if name == "pjit":
                    in_sh = eqn.params.get("in_shardings", ())
                    for sh, inner_v in zip(in_sh, sub.invars):
                        spec = _spec_of(sh)
                        if spec is not None:
                            inner_seed.setdefault(inner_v, (spec, "in_shardings"))
                walk(sub, inner_seed)

    walk(_as_jaxpr(jaxpr), {})
    return findings
