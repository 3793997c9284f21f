"""Static kernel-verifier tests (PR 7 injected-violation style): every
kernel rule family gets a test proving it FIRES on an injected violation
and a test proving it stays quiet on the shipped instantiations — plus
the static-vs-runtime traffic agreement gates and the simulator loader."""

import dataclasses
import json
import os

import numpy as np
import pytest

from repro.analysis import kernel_rules
from repro.analysis.pallas_inspect import (DOUBLE_BUFFER, block_traffic,
                                           check_bounds, iter_grid,
                                           vmem_footprint)
from repro.analysis.report import AuditReport, load_waivers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "benchmarks/baselines/kernel_audit.json")
PAGED_BENCH = os.path.join(REPO, "benchmarks/baselines/paged_attn.json")


def _shipped(name):
    for inst in kernel_rules.registered_instantiations():
        if inst.name == name:
            return inst
    raise KeyError(name)


def _corrupt_table(inst, bi, j, value):
    """Same instantiation, one page-table entry rewritten."""
    table = np.array(inst.scalars[0])
    table[bi, j] = value
    meta = dict(inst.meta, table=table)
    return dataclasses.replace(inst, scalars=(table,) + inst.scalars[1:],
                               meta=meta)


# ---------------------------------------------------------------------------
# rule 1: index-map bounds proofs
# ---------------------------------------------------------------------------


class TestIndexBounds:
    def test_shipped_instantiations_prove_in_bounds(self):
        insts = kernel_rules.registered_instantiations()
        kernels = {i.kernel for i in insts}
        assert kernels == {"paged_attention", "bitplane_matmul",
                           "log2quant"}
        for inst in insts:
            assert not check_bounds(inst), inst.name

    def test_oob_table_entry_flagged(self):
        inst = _shipped("paged_attention/ragged512.s1")
        n_pages = inst.meta["n_pages"]
        bad = _corrupt_table(inst, 0, 3, n_pages + 7)  # past the pool
        f = kernel_rules.rule_index_bounds(bad)
        assert f and all(x.rule == "kernel-index-bounds" for x in f)
        assert any("k_pool" in x.detail for x in f)

    def test_negative_table_entry_flagged(self):
        inst = _shipped("paged_attention/ragged512.s1")
        bad = _corrupt_table(inst, 1, 0, -2)
        assert kernel_rules.rule_index_bounds(bad)

    def test_trash_entry_in_live_zone_flagged(self):
        # slot 0 has 512 tokens = 32 live columns; column 5 -> trash page
        inst = _shipped("paged_attention/ragged512.s1")
        bad = _corrupt_table(inst, 0, 5, inst.meta["trash_page"])
        f = kernel_rules.rule_index_bounds(bad)
        assert f and "unreachable" in f[0].detail

    def test_bad_index_map_arity_flagged(self):
        inst = _shipped("log2quant/decode_f32.b256x512")
        op = inst.inputs[0]
        bad_op = dataclasses.replace(op, index_map=lambda i, j: (i, j, 0))
        bad = dataclasses.replace(inst, inputs=(bad_op,))
        v = check_bounds(bad)
        assert v and "arity" in v[0].detail


# ---------------------------------------------------------------------------
# rule 2: VMEM budgets
# ---------------------------------------------------------------------------


class TestVmemBudget:
    def test_footprint_double_buffers_io_not_scratch(self):
        inst = _shipped("bitplane_matmul/canon_s1.b128")
        fp = vmem_footprint(inst)
        # 4 streamed operands double-buffered + 1 scratch, single
        assert fp["n_buffers"] == 5
        assert fp["buffers"]["planes"] == DOUBLE_BUFFER * 8 * 128 * 128
        assert fp["buffers"]["scratch0"] == 128 * 128 * 4
        assert fp["vmem_bytes"] == sum(fp["buffers"].values())

    def test_over_budget_scratch_fails(self, tmp_path, monkeypatch):
        inst = _shipped("bitplane_matmul/canon_s1.b128")
        fat = dataclasses.replace(
            inst, scratch=inst.scratch + (((4096, 4096), "float32"),))
        assert vmem_footprint(fat)["vmem_bytes"] \
            > kernel_rules.VMEM_LIMIT_BYTES
        monkeypatch.setattr(kernel_rules, "registered_instantiations",
                            lambda: [fat])
        fnd, _ = kernel_rules.run_kernel_audit(
            str(tmp_path / "b.json"), update_baselines=True,
            with_per_tick=False)
        assert any(f.rule == "kernel-vmem-budget"
                   and "cap" in f.detail for f in fnd)

    def test_budget_drift_fails_and_match_passes(self):
        fresh = {"kernels": {"k/c": {"n_buffers": 3, "vmem_bytes": 1000,
                                     "bytes_read": 5, "fetches": {"x": 2}}},
                 "per_tick": {}}
        same = json.loads(json.dumps(fresh))
        assert not kernel_rules.check_kernel_budgets(fresh, same)

        drift = json.loads(json.dumps(fresh))
        drift["kernels"]["k/c"]["n_buffers"] = 4          # exact gate
        f = kernel_rules.check_kernel_budgets(fresh, drift)
        assert f and f[0].rule == "kernel-vmem-budget"

        drift = json.loads(json.dumps(fresh))
        drift["kernels"]["k/c"]["vmem_bytes"] = 1200      # 20% > 10% rtol
        assert kernel_rules.check_kernel_budgets(fresh, drift)

        ok = json.loads(json.dumps(fresh))
        ok["kernels"]["k/c"]["vmem_bytes"] = 1050         # 5% < 10% rtol
        assert not kernel_rules.check_kernel_budgets(fresh, ok)

    def test_unbaselined_instantiation_fails(self):
        fresh = {"kernels": {"k/new": {"n_buffers": 1, "vmem_bytes": 8}},
                 "per_tick": {}}
        f = kernel_rules.check_kernel_budgets(fresh, {"kernels": {}})
        assert f and "no committed budget" in f[0].detail
        # and the stale direction
        f = kernel_rules.check_kernel_budgets({"kernels": {},
                                               "per_tick": {}}, fresh)
        assert f and "no longer registered" in f[0].detail


# ---------------------------------------------------------------------------
# rule 3: padding / masked-tail lints
# ---------------------------------------------------------------------------


class TestUnmaskedTail:
    def test_shipped_instantiations_quiet(self):
        for inst in kernel_rules.registered_instantiations():
            assert not kernel_rules.rule_unmasked_tail(inst), inst.name

    def test_non_dividing_block_flagged(self):
        inst = _shipped("log2quant/decode_f32.b256x512")
        op = inst.inputs[0]
        bad_op = dataclasses.replace(op, shape=(op.shape[0] + 60,
                                                op.shape[1]))
        bad = dataclasses.replace(inst, inputs=(bad_op,))
        f = kernel_rules.rule_unmasked_tail(bad)
        assert f and f[0].rule == "kernel-unmasked-tail"
        assert "does not divide" in f[0].detail

    def test_declared_masked_tail_quiet(self):
        inst = _shipped("log2quant/decode_f32.b256x512")
        op = inst.inputs[0]
        bad_op = dataclasses.replace(op, shape=(op.shape[0] + 60,
                                                op.shape[1]))
        declared = dataclasses.replace(
            inst, inputs=(bad_op,), meta={"masked_dims": {"x": (0,)}})
        assert not kernel_rules.rule_unmasked_tail(declared)

    def test_stale_page_in_dead_zone_flagged(self):
        # slot 3 has 17 tokens = 2 live columns; column 9 -> a real page
        inst = _shipped("paged_attention/ragged512.s1")
        bad = _corrupt_table(inst, 3, 9, 4)
        f = kernel_rules.rule_unmasked_tail(bad)
        assert f and f[0].rule == "kernel-unmasked-tail"
        assert "trash page" in f[0].detail


# ---------------------------------------------------------------------------
# rule 4: static byte-traffic model
# ---------------------------------------------------------------------------


class TestTrafficModel:
    def test_static_matches_runtime_on_ragged512(self):
        """The acceptance gate: the static model derives the measured
        gather_saved_frac bit-for-bit from BlockSpecs x grid x table."""
        inst = _shipped("paged_attention/ragged512.s1")
        rec, disagreements = kernel_rules.static_traffic(inst)
        assert not disagreements
        assert rec["gather_saved_frac"] == 0.5546875
        with open(PAGED_BENCH) as f:
            rows = json.load(f)["rows"]
        assert rec["gather_saved_frac"] == rows["gather_saved_frac"]
        assert float(rec["bytes_read"] + rec["bytes_written"]) \
            == rows["static_bytes_moved"]
        assert float(vmem_footprint(inst)["vmem_bytes"]) \
            == rows["vmem_bytes"]

    def test_split_invariance(self):
        # splitting the page walk must not change bytes moved or pages hit
        r1, _ = kernel_rules.static_traffic(
            _shipped("paged_attention/ragged512.s1"))
        r4, _ = kernel_rules.static_traffic(
            _shipped("paged_attention/ragged512.s4"))
        assert r1["gather_saved_frac"] == r4["gather_saved_frac"]
        assert r1["fetches"]["k_pool"] == r4["fetches"]["k_pool"]

    def test_runtime_disagreement_flagged(self, monkeypatch):
        # force the runtime counter to disagree -> the rule must fire
        from repro.kernels.paged_attention import ops
        inst = _shipped("paged_attention/ragged512.s1")
        monkeypatch.setattr(ops, "gather_traffic_counts",
                            lambda *a, **k: (1.0, 2.0))
        _, disagreements = kernel_rules.static_traffic(inst)
        assert disagreements
        assert disagreements[0].rule == "kernel-traffic-model"

    def test_cell_geometry_walk(self):
        """The qwen2.5-14b-l12 cell's geometry: 33 blocks of 8 pages a
        slot; the useful fetches are exactly its live pages, and the
        copies beyond them stay within ``ppb`` pages a slot."""
        inst = _shipped("paged_attention/qwen14b_decode.s1")
        assert inst.grid == (32, 1, 33) and inst.meta["ppb"] == 8
        rec, disagreements = kernel_rules.static_traffic(inst)
        assert not disagreements
        lens = np.asarray(inst.meta["lengths"])
        assert rec["fetches"]["k_pool"] == int(np.sum(-(-lens // 16))) \
            == 1656
        page = 16 * 8 * 128 * 2                     # one bf16 K or V page
        extra = rec["bytes_issued"] - rec["bytes_read"]
        assert 0 < extra <= 2 * 8 * 32 * page

    def test_dead_columns_not_copied(self):
        """Real (stale) pages in every dead column of the page table: the
        clamped walk of it issues the same copies as of trash columns,
        while walking the table as it stands copies them and trips the
        issued-copies bound."""
        from repro.kernels.paged_attention.kernel import clamped_walk
        inst = _shipped("paged_attention/ragged512.s1")
        meta = inst.meta
        table = np.array(meta["table"])
        for bi, ln in enumerate(meta["lengths"]):
            n = -(-int(ln) // meta["page_len"])
            table[bi, n:] = 1 + np.arange(table.shape[1] - n)
        walk = np.asarray(clamped_walk(table, meta["lengths"],
                                       meta["page_len"], meta["ppb"]))
        base, _ = kernel_rules.static_traffic(inst)
        for scalars, fires in ((walk, False), (table, True)):
            stale = dataclasses.replace(inst, scalars=(scalars,)
                                        + inst.scalars[1:])
            rec, disagreements = kernel_rules.static_traffic(stale)
            assert any("dead blocks are being fetched" in f.detail
                       for f in disagreements) == fires
            if not fires:
                assert not disagreements
                assert rec["bytes_issued"] == base["bytes_issued"]

    def test_bitplane_static_matches_runtime_counters(self):
        import jax.numpy as jnp

        from repro.core.access_model import needed_bits
        from repro.kernels.bitplane_matmul.ops import plane_traffic_counts

        inst = _shipped("bitplane_matmul/canon_s1.b128")
        rec, disagreements = kernel_rules.static_traffic(inst)
        assert not disagreements
        exp = inst.meta["exp"]
        f, t = plane_traffic_counts(jnp.asarray(exp, jnp.int8))
        assert rec["plane_traffic_fraction_tile"] == float(f) / float(t)
        assert rec["element_bits"] == int(jnp.sum(needed_bits(
            jnp.asarray(exp, jnp.int8))))

    def test_bitplane_tampered_skip_table_flagged(self):
        inst = _shipped("bitplane_matmul/canon_s1.b128")
        table = np.array(inst.meta["min_plane"])
        table[0, 0] += 1  # skip one plane too many
        meta = dict(inst.meta, min_plane=table)
        bad = dataclasses.replace(inst, scalars=(table,), meta=meta)
        _, disagreements = kernel_rules.static_traffic(bad)
        assert any("min_plane" in f.detail for f in disagreements)

    def test_pruned_tiles_skip_all_planes(self):
        rec, _ = kernel_rules.static_traffic(
            _shipped("bitplane_matmul/pruned_half.b128"))
        # half the K range is sentinel-pruned: those tiles fetch 0 planes
        assert rec["plane_traffic_fraction_tile"] < 0.55

    def test_revisit_elision(self):
        # out block of the bitplane kernel changes only when (mi, ni)
        # does: K-innermost revisits must not be double-billed
        inst = _shipped("bitplane_matmul/canon_s1.b128")
        tr = block_traffic(inst)
        n_out_blocks = inst.grid[0] * inst.grid[1]
        assert tr["fetches"]["out"] == n_out_blocks
        assert tr["fetches"]["planes"] == len(list(iter_grid(inst.grid)))

    def test_clean_audit_against_committed_baselines(self):
        fnd, rec = kernel_rules.run_kernel_audit(BASELINE,
                                                 with_per_tick=False)
        assert not fnd, [f.key() + ": " + f.detail for f in fnd]
        assert len(rec["kernels"]) >= 9  # 3 kernels x >= 3 cases


# ---------------------------------------------------------------------------
# per-tick composition + the simulator cost table
# ---------------------------------------------------------------------------


class TestPerTickCensus:
    @pytest.fixture(scope="class")
    def census(self):
        return kernel_rules.per_tick_census()

    def test_tick_launch_counts(self, census):
        # 2 tick_steps x 3 layers: 6 attention launches; the quant tick
        # adds 7 bitplane GEMM sites per step = 42 launches
        assert census["paged_kernel"]["kernels"][
            "paged_attention"]["calls"] == 6
        q = census["paged_kernel-quant"]["kernels"]
        assert q["paged_attention"]["calls"] == 6
        assert q["bitplane_matmul"]["calls"] == 42

    def test_census_matches_committed_baseline(self, census):
        with open(BASELINE) as f:
            base = json.load(f)["per_tick"]
        assert not kernel_rules.check_kernel_budgets(
            {"kernels": {}, "per_tick": census},
            {"kernels": {}, "per_tick": base})

    def test_call_count_drift_fails(self, census):
        with open(BASELINE) as f:
            base = json.load(f)["per_tick"]
        drifted = json.loads(json.dumps(census))
        drifted["paged_kernel"]["kernels"]["paged_attention"]["calls"] += 1
        f = kernel_rules.check_kernel_budgets(
            {"kernels": {}, "per_tick": drifted},
            {"kernels": {}, "per_tick": base})
        assert f and f[0].rule == "kernel-traffic-model"
        assert "launches" in f[0].detail

    def test_simulator_loads_cost_table(self):
        from repro.simulator import load_kernel_cost_table
        table = load_kernel_cost_table(BASELINE)
        assert set(table) == {"paged_kernel", "paged_kernel-quant"}
        q = table["paged_kernel-quant"]
        assert q["tick_bytes_total"] == sum(
            v["operand_bytes"] for v in q["kernels"].values())
        assert q["kernels"]["bitplane_matmul"]["calls"] == 42


# ---------------------------------------------------------------------------
# waiver registry validation + report plumbing
# ---------------------------------------------------------------------------


class TestWaiverValidation:
    def test_unknown_rule_id_rejected(self, tmp_path):
        p = tmp_path / "w.json"
        p.write_text(json.dumps({"waivers": [
            {"rule": "kernel-index-bounds-typo", "match": "*",
             "reason": "legit reason"}]}))
        with pytest.raises(ValueError, match="unknown rule"):
            load_waivers(str(p), known_rules=("kernel-index-bounds",))

    def test_known_rule_id_accepted(self, tmp_path):
        p = tmp_path / "w.json"
        p.write_text(json.dumps({"waivers": [
            {"rule": "kernel-index-bounds", "match": "*",
             "reason": "legit reason"}]}))
        ws = load_waivers(str(p), known_rules=("kernel-index-bounds",))
        assert len(ws) == 1

    def test_no_registry_skips_validation(self, tmp_path):
        p = tmp_path / "w.json"
        p.write_text(json.dumps({"waivers": [
            {"rule": "anything", "match": "*", "reason": "r"}]}))
        assert load_waivers(str(p))  # legacy call: no registry, no check

    def test_committed_waiver_file_validates_against_registry(self):
        from repro.analysis.audit import ALL_RULES
        assert set(kernel_rules.KERNEL_RULES) <= set(ALL_RULES)
        load_waivers(os.path.join(REPO, "tools/audit_waivers.json"),
                     known_rules=ALL_RULES)

    def test_report_embeds_kernel_records(self):
        rep = AuditReport(kernels={"kernels": {"k/c": {"vmem_bytes": 1}}})
        doc = json.loads(rep.to_json())
        assert doc["kernels"]["kernels"]["k/c"]["vmem_bytes"] == 1


class TestBenchClassification:
    def test_new_rows_gate_exact(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_check", os.path.join(REPO, "tools/bench_check.py"))
        bc = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bc)
        assert bc.classify("paged_attn.b4.pl16.nb32.vmem_bytes") == "exact"
        assert bc.classify(
            "paged_attn.b4.pl16.nb32.static_bytes_moved") == "exact"
        assert bc.classify(
            "paged_attn.b4.pl16.nb32.kernel_split1_us") == "advisory"
