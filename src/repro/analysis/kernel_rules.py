"""Kernel rule family: the static Pallas verifier (rule family 5).

Four rules over every registered kernel instantiation (the per-kernel
``audit_specs()`` hooks in ``kernels/*/kernel.py``), none of which execute
a kernel:

* ``kernel-index-bounds`` — exhaustive index-map bounds proof
  (:func:`pallas_inspect.check_bounds`) plus the paged-attention validity
  half: a LIVE page-table column (one holding valid tokens) must map to a
  real page, never the reserved trash page — a trash entry in the live
  zone makes valid tokens unreachable and the softmax silently wrong.
* ``kernel-vmem-budget`` — double-buffered block windows + scratch gated
  against ``benchmarks/baselines/kernel_audit.json`` (buffer counts
  exact, bytes at 10% rtol) and an absolute per-core cap.
* ``kernel-unmasked-tail`` — a grid dimension that does not divide its
  operand extent must carry a masked-tail declaration, and the PR 6
  trash-column idiom is enforced: every DEAD page-table column (past the
  last valid page) must point at the trash page, not a stale real page.
* ``kernel-traffic-model`` — bytes moved derived from BlockSpecs x grid x
  dtype (:func:`pallas_inspect.block_traffic`), refined by the plane-skip
  table and the live-page mask, cross-checked EXACTLY against the runtime
  counters (``ops.gather_traffic_counts``, ``ops.plane_traffic_counts``,
  ``core.access_model.needed_bits``) and the committed baselines.  The
  paper's savings numbers become compile-time facts: the static model
  must reproduce the measured ``gather_saved_frac`` bit-for-bit, and the
  per-tick pallas_call census (via the PR 7 program registry) prices a
  whole serve tick in bytes — the cost table ``simulator/`` loads.

Baselines live in ``benchmarks/baselines/kernel_audit.json``; regenerate
with ``tools/audit.py --kernels --update-baselines``.
"""

from __future__ import annotations

import json
from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.analysis.pallas_inspect import (
    KernelInstantiation,
    block_traffic,
    check_bounds,
    extract_pallas_calls,
    vmem_footprint,
)
from repro.analysis.report import Finding

KERNEL_RULES = (
    "kernel-index-bounds",
    "kernel-vmem-budget",
    "kernel-unmasked-tail",
    "kernel-traffic-model",
)

KERNEL_BASELINE_PATH = "benchmarks/baselines/kernel_audit.json"
PAGED_ATTN_BENCH_BASELINE = "benchmarks/baselines/paged_attn.json"

# one TPU core's VMEM; an instantiation above this cannot be resident even
# once, let alone double-buffered
VMEM_LIMIT_BYTES = 16 * 2**20
VMEM_BYTES_RTOL = 0.10
TICK_BYTES_RTOL = 0.10

# kernel-body function name (as pallas records it in the kernel jaxpr's
# debug info)
# -> audit family; the per-tick census keys sites by this
KERNEL_FN_FAMILY = {
    "_paged_attn_kernel": "paged_attention",
    "_paged_attn_quant_kernel": "paged_attention",
    "_bitplane_matmul_kernel": "bitplane_matmul",
    "_log2quant_kernel": "log2quant",
}

# the serve variants whose tick dispatches pallas kernels (PR 7 matrix)
TICK_VARIANTS = ("paged_kernel", "paged_kernel-quant")

MAX_BOUNDS_FINDINGS = 8  # per instantiation: first few violations suffice


def registered_instantiations() -> List[KernelInstantiation]:
    """Every instantiation the kernel packages register — all three
    kernels across their audit matrices (dtypes, tilings, geometries)."""
    from repro.kernels.bitplane_matmul import kernel as bitplane
    from repro.kernels.log2quant import kernel as log2quant
    from repro.kernels.paged_attention import kernel as paged

    out: List[KernelInstantiation] = []
    for mod in (paged, bitplane, log2quant):
        out.extend(mod.audit_specs())
    return out


def _finding(rule: str, inst: KernelInstantiation, detail: str) -> Finding:
    return Finding(rule=rule, variant=inst.kernel, program=inst.case, detail=detail)


# ---------------------------------------------------------------------------
# rule 1: index-map bounds proofs
# ---------------------------------------------------------------------------


def rule_index_bounds(inst: KernelInstantiation) -> List[Finding]:
    out: List[Finding] = []
    violations = check_bounds(inst)
    for v in violations[:MAX_BOUNDS_FINDINGS]:
        out.append(
            _finding("kernel-index-bounds", inst, f"{v.operand} at grid{v.gidx}: {v.detail}")
        )
    if len(violations) > MAX_BOUNDS_FINDINGS:
        out.append(
            _finding(
                "kernel-index-bounds",
                inst,
                f"... and {len(violations) - MAX_BOUNDS_FINDINGS} more " f"bounds violations",
            )
        )

    # validity half for the paged kernel: live columns must be real pages
    if inst.kernel == "paged_attention":
        meta = inst.meta
        table = np.asarray(meta["table"])
        lens = np.asarray(meta["lengths"])
        page_len, trash = int(meta["page_len"]), int(meta["trash_page"])
        for bi in range(table.shape[0]):
            n_live = -(-int(lens[bi]) // page_len)
            for j in range(n_live):
                if int(table[bi, j]) == trash:
                    out.append(
                        _finding(
                            "kernel-index-bounds",
                            inst,
                            f"slot {bi} column {j} holds valid tokens but maps "
                            f"to the trash page {trash} — those tokens are "
                            f"unreachable",
                        )
                    )
    return out


# ---------------------------------------------------------------------------
# rule 3: padding/divisibility lints (the trash-column idiom, checked)
# ---------------------------------------------------------------------------


def rule_unmasked_tail(inst: KernelInstantiation) -> List[Finding]:
    out: List[Finding] = []
    masked = inst.meta.get("masked_dims", {})
    for op in inst.operands:
        declared = set(masked.get(op.name, ()))
        for d, (extent, blk) in enumerate(zip(op.shape, op.block_shape)):
            if extent % blk and d not in declared:
                out.append(
                    _finding(
                        "kernel-unmasked-tail",
                        inst,
                        f"{op.name} dim {d}: block {blk} does not divide "
                        f"extent {extent} and no masked-tail declaration — "
                        f"the last block streams {blk - extent % blk} padding "
                        f"elements into the kernel unmasked",
                    )
                )

    # paged kernel: dead table columns must be trash (PR 6 idiom) — a stale
    # real page there is fetched, masked late, and billed as traffic
    if inst.kernel == "paged_attention":
        meta = inst.meta
        table = np.asarray(meta["table"])
        lens = np.asarray(meta["lengths"])
        page_len, trash = int(meta["page_len"]), int(meta["trash_page"])
        for bi in range(table.shape[0]):
            n_live = -(-int(lens[bi]) // page_len)
            for j in range(n_live, table.shape[1]):
                if int(table[bi, j]) != trash:
                    out.append(
                        _finding(
                            "kernel-unmasked-tail",
                            inst,
                            f"slot {bi} column {j} is past the last valid page "
                            f"({n_live}) but maps to page {int(table[bi, j])} "
                            f"instead of the trash page — stale mapping, "
                            f"unmasked tail traffic",
                        )
                    )
    return out


# ---------------------------------------------------------------------------
# rule 4: static byte-traffic model (+ exact runtime agreement)
# ---------------------------------------------------------------------------


def _traffic_paged(inst: KernelInstantiation) -> Tuple[Dict, List[Finding]]:
    meta = inst.meta
    table = np.asarray(meta["table"])
    lens = np.asarray(meta["lengths"])
    page_len, bps = int(meta["page_len"]), int(meta["bps"])

    def live(name: str, gidx: Tuple[int, ...]) -> bool:
        if name not in ("k_pool", "v_pool", "k_scale", "v_scale"):
            return True
        bi, si, ji = gidx
        return si * bps + ji < -(-int(lens[bi]) // page_len)

    tr = block_traffic(inst, live=live)

    # the static gather fraction: pages the table walk touches, per slot
    # (one fetch per page: a block carries every kv head of its page)
    static_touched = tr["fetches"]["k_pool"]
    total = table.shape[0] * table.shape[1]
    saved_frac = 1.0 - static_touched / total

    findings: List[Finding] = []
    from repro.kernels.paged_attention.ops import gather_traffic_counts

    rt_touched, rt_total = gather_traffic_counts(table, lens, page_len)
    if (float(static_touched), float(total)) != (rt_touched, rt_total):
        findings.append(
            _finding(
                "kernel-traffic-model",
                inst,
                f"static page walk touches {static_touched}/{total} pages but "
                f"the runtime counter says {rt_touched:.0f}/{rt_total:.0f} — "
                f"one of the two models is wrong",
            )
        )
    if tr["fetches"]["v_pool"] != tr["fetches"]["k_pool"]:
        findings.append(
            _finding(
                "kernel-traffic-model",
                inst,
                f"k_pool and v_pool disagree on fetches "
                f"({tr['fetches']['k_pool']} vs {tr['fetches']['v_pool']}) — "
                f"their index maps must walk the same pages",
            )
        )

    record = {
        "bytes_read": int(sum(tr["read"].values())),
        "bytes_written": int(sum(tr["written"].values())),
        "fetches": {k: int(v) for k, v in sorted(tr["fetches"].items())},
        "gather_saved_frac": saved_frac,
    }
    if "k_scale" in tr["fetches"]:
        # quantized pool: page bytes actually streamed (packed codes +
        # per-page scales) vs the same page walk over a dense f32 pool —
        # the compressed-page traffic saving, as a gated exact number.
        kp = next(op for op in inst.inputs if op.name == "k_pool")
        itemsize = np.dtype(kp.dtype).itemsize
        q_bytes = sum(tr["read"][n]
                      for n in ("k_pool", "v_pool", "k_scale", "v_scale"))
        dense_bytes = (tr["read"]["k_pool"] + tr["read"]["v_pool"]) * (
            4.0 / itemsize)
        record["page_read_saved_frac"] = 1.0 - q_bytes / dense_bytes
    return record, findings


def _traffic_bitplane(inst: KernelInstantiation) -> Tuple[Dict, List[Finding]]:
    meta = inst.meta
    exp = np.asarray(meta["exp"], np.int64)
    bits, n_bits = int(meta["bits"]), int(meta["n_bits"])
    bm, bk = int(meta["block_m"]), int(meta["block_k"])
    prefetched = np.asarray(meta["min_plane"])
    findings: List[Finding] = []

    # independent numpy recompute of the skip table — the scalar operand
    # the kernel will actually prefetch must agree with it
    sentinel = -(1 << (n_bits - 1))
    m, k = exp.shape
    e4 = exp.reshape(m // bm, bm, k // bk, bk).swapaxes(1, 2)
    alive4 = e4 != sentinel
    max_e = np.max(np.where(alive4, e4, -128), axis=(2, 3))
    table = np.where(np.any(alive4, axis=(2, 3)), np.clip(-max_e, 0, bits), bits).astype(np.int64)
    if not np.array_equal(table, prefetched):
        findings.append(
            _finding(
                "kernel-traffic-model",
                inst,
                "scalar-prefetch min_plane table disagrees with the numpy "
                "recompute from the exponents — skip accounting is broken",
            )
        )

    # tile-granular plane traffic: what the kernel's @pl.when skip fetches
    fetched_tiles = int(np.sum(bits - table))
    total_tiles = int(bits * table.size)
    frac_tile = fetched_tiles / total_tiles

    import jax.numpy as jnp

    from repro.kernels.bitplane_matmul.ops import plane_traffic_counts

    rt_f, rt_t = plane_traffic_counts(
        jnp.asarray(exp, jnp.int8), n_bits=n_bits, block_m=bm, block_k=bk, bits=bits
    )
    if (float(fetched_tiles), float(total_tiles)) != (float(rt_f), float(rt_t)):
        findings.append(
            _finding(
                "kernel-traffic-model",
                inst,
                f"static tile count {fetched_tiles}/{total_tiles} != runtime "
                f"plane_traffic_counts {float(rt_f):.0f}/{float(rt_t):.0f}",
            )
        )

    # element-granular bits: the paper's per-activation needed-bits sum,
    # recomputed in numpy and cross-checked against core.access_model
    alive = exp != sentinel
    nb_elem = np.clip(bits + np.minimum(exp, 0), 0, bits)
    element_bits = int(np.sum(np.where(alive, nb_elem, 0)))
    dense_bits = int(np.sum(alive)) * bits

    from repro.core.access_model import needed_bits

    rt_bits = int(
        jnp.sum(needed_bits(jnp.asarray(exp, jnp.int8), n_bits=n_bits, weight_bits=bits))
    )
    if element_bits != rt_bits:
        findings.append(
            _finding(
                "kernel-traffic-model",
                inst,
                f"static element bits {element_bits} != access_model "
                f"needed_bits sum {rt_bits}",
            )
        )

    def refine(name: str, gidx: Tuple[int, ...], nominal: float) -> float:
        if name != "planes":
            return nominal
        mi, _, ki = gidx
        return nominal * (bits - int(table[mi, ki])) / bits

    tr = block_traffic(inst, refine_bytes=refine)
    record = {
        "bytes_read": int(sum(tr["read"].values())),
        "bytes_written": int(sum(tr["written"].values())),
        "fetches": {k: int(v) for k, v in sorted(tr["fetches"].items())},
        "plane_traffic_fraction_tile": frac_tile,
        "element_bits": element_bits,
        "dense_element_bits": dense_bits,
    }
    return record, findings


def _traffic_log2quant(inst: KernelInstantiation) -> Tuple[Dict, List[Finding]]:
    tr = block_traffic(inst)
    record = {
        "bytes_read": int(sum(tr["read"].values())),
        "bytes_written": int(sum(tr["written"].values())),
        "fetches": {k: int(v) for k, v in sorted(tr["fetches"].items())},
    }
    return record, []


_TRAFFIC_BY_FAMILY: Dict[str, Callable] = {
    "paged_attention": _traffic_paged,
    "bitplane_matmul": _traffic_bitplane,
    "log2quant": _traffic_log2quant,
}


def static_traffic(inst: KernelInstantiation) -> Tuple[Dict, List[Finding]]:
    """(record, agreement findings) for one instantiation."""
    return _TRAFFIC_BY_FAMILY[inst.kernel](inst)


# ---------------------------------------------------------------------------
# per-tick census: compose statics over the serve programs (PR 7 registry)
# ---------------------------------------------------------------------------


def per_tick_census(log=lambda msg: None) -> Dict[str, Dict]:
    """Every pallas_call a kernel-enabled serve tick dispatches, with scan
    trip counts multiplied through: ``{variant: {"kernels": {family:
    {"calls", "operand_bytes"}}, "tick_bytes_total"}}`` — calls are the
    exact per-tick launch bill, bytes the dense streaming upper bound the
    simulator prices (savings fractions come from the matching audit
    case)."""
    from repro.analysis.jaxpr_rules import make_program_jaxpr
    from repro.analysis.programs import Variant, audit_model, build_scheduler

    cfg, params = audit_model()
    out: Dict[str, Dict] = {}
    for quant in (False, True):
        variant = Variant("paged_kernel", quant, None)
        log(f"  tracing {variant.name}/tick for the kernel census...")
        sched = build_scheduler(variant, cfg=cfg, params=params)
        fn, args = sched.audit_programs()["tick"]
        sites = extract_pallas_calls(make_program_jaxpr(fn, args))
        kernels: Dict[str, Dict[str, int]] = {}
        for site in sites:
            family = KERNEL_FN_FAMILY.get(site.kernel_name, site.kernel_name)
            rec = kernels.setdefault(family, {"calls": 0, "operand_bytes": 0})
            rec["calls"] += site.multiplier
            rec["operand_bytes"] += site.multiplier * site.operand_bytes
        out[variant.name] = {
            "kernels": {k: kernels[k] for k in sorted(kernels)},
            "tick_bytes_total": int(sum(r["operand_bytes"] for r in kernels.values())),
        }
    return out


# ---------------------------------------------------------------------------
# baseline I/O + gates
# ---------------------------------------------------------------------------


def load_kernel_baseline(path: str = KERNEL_BASELINE_PATH) -> Dict:
    with open(path) as f:
        return json.load(f)


def save_kernel_baseline(records: Dict, path: str = KERNEL_BASELINE_PATH) -> None:
    doc = {
        "note": (
            "static kernel-audit budgets (VMEM + byte-traffic model) "
            "— regenerate with tools/audit.py --kernels "
            "--update-baselines"
        ),
        "kernels": {k: records["kernels"][k] for k in sorted(records["kernels"])},
        "per_tick": records.get("per_tick", {}),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def _f(rule: str, key: str, detail: str) -> Finding:
    variant, _, program = key.partition("/")
    return Finding(rule=rule, variant=variant, program=program, detail=detail)


def check_kernel_budgets(
    fresh: Dict,
    baseline: Dict,
    *,
    vmem_rtol: float = VMEM_BYTES_RTOL,
    tick_rtol: float = TICK_BYTES_RTOL,
) -> List[Finding]:
    """Gate fresh records against the committed baseline.

    * VMEM: buffer counts exact, bytes at ``vmem_rtol`` (block shapes are
      deliberate choices; byte totals may shift with dtype swaps).
    * traffic: EXACT — fetch counts, byte totals, and the savings metrics
      are deterministic integer arithmetic; any drift is a model change
      that must be recommitted deliberately.
    * per-tick census: calls exact, bytes at ``tick_rtol`` (operand
      shapes ride the smoke-model config).
    * a case missing from either side is itself a finding.
    """
    out: List[Finding] = []
    fresh_k = fresh.get("kernels", {})
    base_k = baseline.get("kernels", {})
    for key in sorted(set(fresh_k) | set(base_k)):
        if key not in base_k:
            out.append(
                _f(
                    "kernel-vmem-budget",
                    key,
                    "instantiation has no committed budget — run "
                    "tools/audit.py --kernels --update-baselines",
                )
            )
            continue
        if key not in fresh_k:
            out.append(
                _f(
                    "kernel-vmem-budget",
                    key,
                    "instantiation in baseline but no longer "
                    "registered — run tools/audit.py --kernels "
                    "--update-baselines",
                )
            )
            continue
        got, want = fresh_k[key], base_k[key]
        if int(got["n_buffers"]) != int(want["n_buffers"]):
            out.append(
                _f(
                    "kernel-vmem-budget",
                    key,
                    f"n_buffers {got['n_buffers']} != budget " f"{want['n_buffers']} (exact gate)",
                )
            )
        gb, wb = float(got["vmem_bytes"]), float(want["vmem_bytes"])
        rel = abs(gb - wb) / max(abs(wb), 1.0)
        if rel > vmem_rtol:
            out.append(
                _f(
                    "kernel-vmem-budget",
                    key,
                    f"vmem_bytes {gb:.0f} vs budget {wb:.0f} " f"(rel {rel:.1%} > {vmem_rtol:.0%})",
                )
            )
        for field in sorted(set(got) | set(want)):
            if field in ("n_buffers", "vmem_bytes"):
                continue
            if got.get(field) != want.get(field):
                out.append(
                    _f(
                        "kernel-traffic-model",
                        key,
                        f"{field} {got.get(field)!r} != committed "
                        f"{want.get(field)!r} (exact gate: the "
                        f"static model is deterministic)",
                    )
                )

    fresh_t = fresh.get("per_tick", {})
    base_t = baseline.get("per_tick", {})
    for name in sorted(set(fresh_t) | set(base_t)):
        key = f"{name}/tick"
        if name not in base_t or name not in fresh_t:
            out.append(
                _f(
                    "kernel-traffic-model",
                    key,
                    "per-tick census missing on one side — run "
                    "tools/audit.py --kernels --update-baselines",
                )
            )
            continue
        got, want = fresh_t[name], base_t[name]
        gk, wk = got["kernels"], want["kernels"]
        for fam in sorted(set(gk) | set(wk)):
            g = int(gk.get(fam, {}).get("calls", 0))
            w = int(wk.get(fam, {}).get("calls", 0))
            if g != w:
                out.append(
                    _f(
                        "kernel-traffic-model",
                        key,
                        f"{fam} launches {g} != budget {w} per tick "
                        f"(exact gate: every launch is per-tick "
                        f"serving cost)",
                    )
                )
            gb = float(gk.get(fam, {}).get("operand_bytes", 0))
            wb = float(wk.get(fam, {}).get("operand_bytes", 0))
            rel = abs(gb - wb) / max(abs(wb), 1.0)
            if rel > tick_rtol:
                out.append(
                    _f(
                        "kernel-traffic-model",
                        key,
                        f"{fam} operand bytes {gb:.3e} vs budget "
                        f"{wb:.3e} (rel {rel:.1%} > "
                        f"{tick_rtol:.0%})",
                    )
                )
    return out


def check_bench_agreement(
    fresh: Dict, *, bench_path: str = PAGED_ATTN_BENCH_BASELINE
) -> List[Finding]:
    """The cross-file exact gate: the static model's ragged512 gather
    fraction must reproduce the MEASURED bench baseline bit-for-bit —
    this is the acceptance criterion that makes the paper's access-saving
    claim a compile-time fact."""
    key = "paged_attention/ragged512.s1"
    rec = fresh.get("kernels", {}).get(key)
    if rec is None:
        return [
            _f(
                "kernel-traffic-model",
                key,
                "ragged512.s1 not registered — the bench-agreement " "gate has nothing to check",
            )
        ]
    try:
        with open(bench_path) as f:
            measured = json.load(f)["rows"]["gather_saved_frac"]
    except (FileNotFoundError, KeyError):
        return [
            _f(
                "kernel-traffic-model",
                key,
                f"no measured gather_saved_frac in {bench_path} — "
                f"run benchmarks/kernel_bench.py first",
            )
        ]
    static = rec["gather_saved_frac"]
    if float(static) != float(measured):
        return [
            _f(
                "kernel-traffic-model",
                key,
                f"static gather_saved_frac {static!r} != measured "
                f"{measured!r} in {bench_path} (exact gate: static "
                f"and runtime must agree)",
            )
        ]
    return []


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_kernel_audit(
    baseline_path: str = KERNEL_BASELINE_PATH,
    *,
    update_baselines: bool = False,
    with_per_tick: bool = True,
    log=lambda msg: None,
) -> Tuple[List[Finding], Dict]:
    """The kernel rule family end to end: sweep every registered
    instantiation, run rules 1-4, gate (or rewrite) the baselines.
    Returns ``(findings, records)``; ``records`` is what the report embeds
    and ``save_kernel_baseline`` writes."""
    findings: List[Finding] = []
    records: Dict = {"kernels": {}, "per_tick": {}}

    for inst in registered_instantiations():
        log(f"  kernel-audit {inst.name} (grid {inst.grid}, " f"{inst.grid_points} points)...")
        findings += rule_index_bounds(inst)
        findings += rule_unmasked_tail(inst)
        fp = vmem_footprint(inst)
        if fp["vmem_bytes"] > VMEM_LIMIT_BYTES:
            findings.append(
                _finding(
                    "kernel-vmem-budget",
                    inst,
                    f"vmem_bytes {fp['vmem_bytes']} exceeds the "
                    f"{VMEM_LIMIT_BYTES} per-core cap — the kernel cannot be "
                    f"resident",
                )
            )
        traffic_rec, agree = static_traffic(inst)
        findings += agree
        records["kernels"][inst.name] = {
            "n_buffers": fp["n_buffers"],
            "vmem_bytes": fp["vmem_bytes"],
            **traffic_rec,
        }

    if with_per_tick:
        records["per_tick"] = per_tick_census(log=log)

    if update_baselines:
        save_kernel_baseline(records, baseline_path)
        log(f"wrote {len(records['kernels'])} kernel budgets -> " f"{baseline_path}")
    else:
        baseline = load_kernel_baseline(baseline_path)
        if not with_per_tick:
            # partial run: gate only what was computed, as run_audit does
            # for budget-skipping device-limited runs
            baseline = {**baseline, "per_tick": {}}
        findings += check_kernel_budgets(records, baseline)
        findings += check_bench_agreement(records)
    return findings, records


# ---------------------------------------------------------------------------
# the simulator-facing cost table
# ---------------------------------------------------------------------------


def kernel_cost_table(records: Dict) -> Dict[str, Dict]:
    """Flatten per-tick records into the shape
    ``simulator.config.load_kernel_cost_table`` returns: per variant, the
    per-tick launch counts and dense byte bill per kernel family."""
    out: Dict[str, Dict] = {}
    for name, rec in records.get("per_tick", {}).items():
        out[name] = {
            "tick_bytes_total": int(rec["tick_bytes_total"]),
            "kernels": {
                k: {"calls": int(v["calls"]), "operand_bytes": int(v["operand_bytes"])}
                for k, v in rec["kernels"].items()
            },
        }
    return out
