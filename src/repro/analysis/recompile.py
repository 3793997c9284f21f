"""Recompile audit (rule family 4): the compiled-program census, enforced.

The other three rule families are strictly static (trace/lower, nothing
runs).  This one cannot be: whether the scheduler RE-compiles under real
traffic is a property of its caching behavior, not of any single traced
program.  So this family drives a scripted traffic sweep through a live
``ServeScheduler`` on the tiny smoke model and asserts the census:

* ``prefill`` compiles once per *bucket used*, never per prompt length;
* ``tick`` / ``write`` / ``chunk`` / ``mixed`` compile exactly once —
  chunked ingestion is ONE slab shape regardless of prompt length;
* replaying the same traffic shapes leaves every count unchanged
  (zero warm-path recompiles);
* the generate-program LRU keys on the mesh fingerprint — an unsharded
  and a sharded build of the SAME configuration must occupy two distinct
  entries (a collision silently reuses the other variant's program).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from repro.analysis.report import Finding

VARIANT = "recompile-sweep"


def check_census(
    census: Dict[str, int],
    expect: Dict[str, int],
    variant: str = VARIANT,
    *,
    stage: str = "census",
) -> List[Finding]:
    """Compare an observed compile census against the expected one —
    exact, including a probe-unavailable (-1) guard."""
    out: List[Finding] = []
    for prog in sorted(set(census) | set(expect)):
        got = census.get(prog)
        want = expect.get(prog)
        if got is None or want is None:
            out.append(
                Finding(
                    rule="recompile-census",
                    variant=variant,
                    program=str(prog),
                    detail=(
                        f"{stage}: program present on one side only "
                        f"(got={got}, want={want})"
                    ),
                )
            )
        elif got == -1:
            out.append(
                Finding(
                    rule="recompile-census",
                    variant=variant,
                    program=str(prog),
                    detail=(
                        f"{stage}: compiled-program probe unavailable "
                        f"(jax dropped _cache_size?)"
                    ),
                )
            )
        elif got != want:
            out.append(
                Finding(
                    rule="recompile-census",
                    variant=variant,
                    program=str(prog),
                    detail=(
                        f"{stage}: {got} compiled programs, expected {want} "
                        f"(shape-keyed retrace leak)"
                    ),
                )
            )
    return out


def _sweep(sched, prompts: List[Tuple[int, int]]) -> None:
    """Submit (length, max_new) prompts and drain the scheduler."""
    rng = np.random.default_rng(0)
    for length, max_new in prompts:
        sched.submit(rng.integers(0, sched.cfg.vocab_size, size=length, dtype=np.int32), max_new)
    sched.run()


def run_recompile_audit() -> Tuple[List[Finding], Dict[str, int]]:
    """The scripted traffic sweep (see module docstring).  Returns
    (findings, final census) — an empty findings list is the pass."""
    from repro.analysis.programs import (
        AUDIT_BUCKETS,
        AUDIT_CHUNK_LEN,
        AUDIT_MAX_LEN,
        AUDIT_SLOTS,
        AUDIT_TICK_STEPS,
        audit_model,
    )
    from repro.serving import engine
    from repro.serving.config import ServeConfig
    from repro.serving.scheduler import ServeScheduler

    cfg, params = audit_model()
    sched = ServeScheduler(
        cfg,
        params,
        ServeConfig(
            max_slots=AUDIT_SLOTS,
            max_len=AUDIT_MAX_LEN,
            buckets=AUDIT_BUCKETS,
            tick_steps=AUDIT_TICK_STEPS,
            chunked="auto",
            chunk_len=AUDIT_CHUNK_LEN,
        ),
    )
    findings: List[Finding] = []

    # phase 1: one over-bucket prompt ALONE — its ingestion runs chunk-only
    # ticks (no decode rows live yet), so the chunk program compiles here
    _sweep(sched, [(20, 4)])
    # phase 2: mixed traffic — both buckets, plus an over-bucket prompt
    # ingesting WHILE others decode (compiles the mixed program)
    _sweep(sched, [(5, 6), (12, 6), (24, 6), (7, 4)])
    expect = {"prefill": len(AUDIT_BUCKETS), "tick": 1, "write_slot": 1, "chunk": 1, "mixed": 1}
    findings += check_census(sched.compile_stats(), expect, stage="cold")

    # phase 3: REPLAY different lengths hitting the same buckets/chunks —
    # the warm path must not compile anything new
    _sweep(sched, [(6, 4), (11, 5), (26, 4), (3, 3)])
    findings += check_census(sched.compile_stats(), expect, stage="warm")

    # mesh-fingerprint collision check: same configuration, unsharded vs a
    # degenerate 1x1 mesh — two distinct generate-LRU entries (building the
    # jitted wrappers compiles nothing)
    from repro.launch.mesh import auto_mesh

    fp_none = engine.mesh_fingerprint(None)
    mesh = auto_mesh((1, 1), ("data", "model"))
    fp_mesh = engine.mesh_fingerprint(mesh)
    if fp_none == fp_mesh:
        findings.append(
            Finding(
                rule="recompile-census",
                variant=VARIANT,
                program="generate_fn",
                detail=(
                    "mesh_fingerprint(None) == mesh_fingerprint(1x1 mesh): "
                    "sharded/unsharded programs would collide in the LRU"
                ),
            )
        )
    before = len(engine.generate_fn)
    fn_plain = engine.generate_fn(cfg, 4, 0.0, False, None, False, mesh=None)
    fn_mesh = engine.generate_fn(cfg, 4, 0.0, False, None, False, mesh=mesh)
    grew = len(engine.generate_fn) - before
    if fn_plain is fn_mesh or grew < 2:
        findings.append(
            Finding(
                rule="recompile-census",
                variant=VARIANT,
                program="generate_fn",
                detail=(
                    f"mesh-fingerprint cache collision: unsharded and 1x1-"
                    f"mesh builds share a program (cache grew {grew}, "
                    f"expected 2)"
                ),
            )
        )

    return findings, sched.compile_stats()
