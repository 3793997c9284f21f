"""Program-auditor tests: every rule family gets (a) an injected-violation
test proving the rule FIRES and (b) a clean-program test proving it stays
quiet — plus waiver/report plumbing and a live single-variant audit."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import jaxpr_rules, sharding_rules
from repro.analysis.budgets import check_budgets
from repro.analysis.recompile import check_census
from repro.analysis.report import (AuditReport, Finding, Waiver,
                                   apply_waivers, load_waivers)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# rule family 1: jaxpr rules
# ---------------------------------------------------------------------------


class TestJaxprRules:
    def test_debug_callback_flagged(self):
        def bad(x):
            jax.debug.print("leftover {x}", x=x[0])
            return x * 2

        j = jax.make_jaxpr(bad)(jnp.zeros((4,)))
        f = jaxpr_rules.rule_no_host_callback(j, "v", "p")
        assert f and f[0].rule == "no-host-callback"
        assert "debug_print" in f[0].detail

    def test_callback_inside_scan_flagged(self):
        # the rule must see through lax.scan's body jaxpr
        def bad(x):
            def body(c, _):
                jax.debug.print("tick {c}", c=c[0])
                return c * 2, c

            return jax.lax.scan(body, x, None, length=3)

        j = jax.make_jaxpr(bad)(jnp.zeros((4,)))
        assert jaxpr_rules.rule_no_host_callback(j, "v", "p")

    def test_clean_program_quiet(self):
        j = jax.make_jaxpr(lambda x: jnp.tanh(x) * 2)(jnp.zeros((4,)))
        assert not jaxpr_rules.rule_no_host_callback(j, "v", "p")
        assert not jaxpr_rules.rule_no_double_precision(j, "v", "p")
        assert not jaxpr_rules.rule_no_integer_upcast(j, "v", "p")

    def test_f64_flagged(self):
        with jax.enable_x64(True):
            j = jax.make_jaxpr(
                lambda x: x.astype(jnp.float64) + 1.0)(
                    jnp.zeros((3,), jnp.float32))
        f = jaxpr_rules.rule_no_double_precision(j, "v", "p")
        assert f and f[0].rule == "no-double-precision"
        assert "float64" in f[0].detail

    def test_i64_flagged(self):
        with jax.enable_x64(True):
            j = jax.make_jaxpr(
                lambda x: x.astype(jnp.int64) * 2)(
                    jnp.zeros((3,), jnp.int32))
        f = jaxpr_rules.rule_no_integer_upcast(j, "v", "p")
        assert f and f[0].rule == "no-integer-upcast"
        assert "int64" in f[0].detail


class TestDensePoolGather:
    N_PAGES = 34

    def test_dense_gather_flagged(self):
        pool = jnp.zeros((2, self.N_PAGES, 4, 8), jnp.float32)
        table = jnp.zeros((3,), jnp.int32)

        def bad(pool, table):
            return pool[:, table]           # dense pool[table] fallback

        j = jax.make_jaxpr(bad)(pool, table)
        f = jaxpr_rules.rule_no_dense_pool_gather(
            j, "v", "tick", n_pages=self.N_PAGES)
        assert f and f[0].rule == "no-dense-pool-gather"

    def test_integer_index_gather_quiet(self):
        # page-table index arithmetic (int gathers) must pass
        table = jnp.zeros((4, 8), jnp.int32)
        idx = jnp.zeros((3,), jnp.int32)
        j = jax.make_jaxpr(lambda t, i: t[:, i])(table, idx)
        assert not jaxpr_rules.rule_no_dense_pool_gather(
            j, "v", "tick", n_pages=self.N_PAGES)

    def test_float_gather_off_pool_quiet(self):
        # float gather NOT carrying the page axis is not the pool
        x = jnp.zeros((2, 16, 8), jnp.float32)
        idx = jnp.zeros((3,), jnp.int32)
        j = jax.make_jaxpr(lambda x, i: x[:, i])(x, idx)
        assert not jaxpr_rules.rule_no_dense_pool_gather(
            j, "v", "tick", n_pages=self.N_PAGES)

    def test_real_paged_tick_without_kernel_has_dense_gather(self):
        # positive control on a REAL program: kernel off -> the tick's
        # attention gathers pool[table] densely, and the rule sees it
        from repro.analysis.programs import (AUDIT_N_PAGES, Variant,
                                             build_scheduler)
        sched = build_scheduler(Variant("paged", False, None))
        fn, args = sched.audit_programs()["tick"]
        j = jaxpr_rules.make_program_jaxpr(fn, args)
        assert jaxpr_rules.rule_no_dense_pool_gather(
            j, "paged", "tick", n_pages=AUDIT_N_PAGES)

    def test_real_kernel_tick_clean(self):
        # the PR 6 kernel's whole point: no dense pool gather in tick
        from repro.analysis.programs import (AUDIT_N_PAGES, Variant,
                                             build_scheduler)
        sched = build_scheduler(Variant("paged_kernel", False, None))
        fn, args = sched.audit_programs()["tick"]
        j = jaxpr_rules.make_program_jaxpr(fn, args)
        assert not jaxpr_rules.rule_no_dense_pool_gather(
            j, "paged_kernel", "tick", n_pages=AUDIT_N_PAGES)


# ---------------------------------------------------------------------------
# rule family 2: sharded-rearrange hazard
# ---------------------------------------------------------------------------


class TestShardedRearrange:
    @pytest.fixture()
    def mesh(self):
        # degenerate 1x1 mesh: PartitionSpec bookkeeping is identical to a
        # real mesh, so the rule is testable on one device
        from repro.launch.mesh import auto_mesh
        return auto_mesh((1, 1), ("data", "model"))

    def test_model_sharded_concat_flagged(self, mesh):
        s = NamedSharding(mesh, P(None, "model"))

        def bad(x):
            x = jax.lax.with_sharding_constraint(x, s)
            return jnp.concatenate([x, x], axis=1)

        j = jax.make_jaxpr(bad)(jnp.zeros((4, 8)))
        f = sharding_rules.rule_sharded_rearrange(j, "v", "p")
        assert f and f[0].rule == "sharded-rearrange"
        assert "concatenate" in f[0].detail

    def test_replicated_pin_quiet(self, mesh):
        s = NamedSharding(mesh, P(None, None))

        def good(x):
            x = jax.lax.with_sharding_constraint(x, s)
            return jnp.concatenate([x, x], axis=1)

        j = jax.make_jaxpr(good)(jnp.zeros((4, 8)))
        assert not sharding_rules.rule_sharded_rearrange(j, "v", "p")

    def test_concat_on_unsharded_axis_quiet(self, mesh):
        # model on dim 1, concat along dim 0: legal
        s = NamedSharding(mesh, P(None, "model"))

        def good(x):
            x = jax.lax.with_sharding_constraint(x, s)
            return jnp.concatenate([x, x], axis=0)

        j = jax.make_jaxpr(good)(jnp.zeros((4, 8)))
        assert not sharding_rules.rule_sharded_rearrange(j, "v", "p")

    def test_pin_survives_dtype_convert(self, mesh):
        # convert_element_type is spec-transparent: still flagged
        s = NamedSharding(mesh, P(None, "model"))

        def bad(x):
            x = jax.lax.with_sharding_constraint(x, s)
            x = x.astype(jnp.bfloat16)
            return jnp.split(x, 2, axis=1)

        j = jax.make_jaxpr(bad)(jnp.zeros((4, 8)))
        f = sharding_rules.rule_sharded_rearrange(j, "v", "p")
        assert f

    def test_reshape_merging_model_dim_flagged(self, mesh):
        s = NamedSharding(mesh, P(None, "model", None))

        def bad(x):
            x = jax.lax.with_sharding_constraint(x, s)
            return x.reshape(4, 32)         # merges the model-sharded dim

        j = jax.make_jaxpr(bad)(jnp.zeros((4, 8, 4)))
        f = sharding_rules.rule_sharded_rearrange(j, "v", "p")
        assert f and "reshape" in f[0].detail

    def test_unpinned_tensor_untracked(self, mesh):
        # no adjacent pin -> the rule does not guess
        j = jax.make_jaxpr(
            lambda x: jnp.concatenate([x, x], axis=1))(jnp.zeros((4, 8)))
        assert not sharding_rules.rule_sharded_rearrange(j, "v", "p")


# ---------------------------------------------------------------------------
# rule family 3: HLO budgets
# ---------------------------------------------------------------------------


class TestBudgetGate:
    BASE = {"chunked@2x2/tick": {
        "collectives": {"all-reduce": 32, "all-gather": 24},
        "collective_bytes": {"all-reduce": 8000.0, "all-gather": 6000.0},
        "traffic_bytes": 1.0e6,
    }}

    def _fresh(self, **over):
        f = json.loads(json.dumps(self.BASE))
        f["chunked@2x2/tick"].update(over)
        return f

    def test_identical_budgets_pass(self):
        assert not check_budgets(self._fresh(), self.BASE)

    def test_extra_collective_launch_fails_exact(self):
        fresh = self._fresh(
            collectives={"all-reduce": 33, "all-gather": 24})
        f = check_budgets(fresh, self.BASE)
        assert f and f[0].rule == "hlo-budget"
        assert "all-reduce count 33 != budget 32" in f[0].detail

    def test_new_collective_kind_fails(self):
        fresh = self._fresh(collectives={"all-reduce": 32, "all-gather": 24,
                                         "all-to-all": 2})
        assert check_budgets(fresh, self.BASE)

    def test_bytes_within_rtol_pass(self):
        fresh = self._fresh(traffic_bytes=1.05e6)    # 5% < 10% rtol
        assert not check_budgets(fresh, self.BASE)

    def test_bytes_outside_rtol_fail(self):
        fresh = self._fresh(traffic_bytes=1.5e6)     # 50% > 10% rtol
        f = check_budgets(fresh, self.BASE)
        assert f and "traffic_bytes" in f[0].detail

    def test_unbaselined_program_fails(self):
        fresh = dict(self._fresh())
        fresh["paged@2x2/tick"] = fresh["chunked@2x2/tick"]
        f = check_budgets(fresh, self.BASE)
        assert any("no committed budget" in x.detail for x in f)

    def test_stale_baseline_entry_fails(self):
        f = check_budgets({}, self.BASE)
        assert any("no longer audited" in x.detail for x in f)


# ---------------------------------------------------------------------------
# rule family 4: recompile census
# ---------------------------------------------------------------------------


class TestRecompileCensus:
    def test_census_match_quiet(self):
        assert not check_census({"tick": 1, "prefill": 2},
                                {"tick": 1, "prefill": 2})

    def test_retrace_leak_flagged(self):
        f = check_census({"tick": 3, "prefill": 2},
                         {"tick": 1, "prefill": 2})
        assert f and f[0].rule == "recompile-census"
        assert "3 compiled programs, expected 1" in f[0].detail

    def test_probe_unavailable_flagged(self):
        f = check_census({"tick": -1}, {"tick": 1})
        assert f and "probe unavailable" in f[0].detail

    def test_missing_program_flagged(self):
        assert check_census({"tick": 1}, {"tick": 1, "chunk": 1})


# ---------------------------------------------------------------------------
# waivers / report
# ---------------------------------------------------------------------------


class TestWaivers:
    def test_reasonless_waiver_rejected(self, tmp_path):
        p = tmp_path / "w.json"
        p.write_text(json.dumps(
            {"waivers": [{"rule": "r", "match": "*", "reason": "  "}]}))
        with pytest.raises(ValueError, match="reason"):
            load_waivers(str(p))

    def test_waiver_glob_covers(self):
        w = Waiver(rule="hlo-budget", match="paged*/tick", reason="by design")
        assert w.covers(Finding(rule="hlo-budget", variant="paged@2x2",
                                program="tick", detail=""))
        assert not w.covers(Finding(rule="hlo-budget", variant="paged@2x2",
                                    program="mixed", detail=""))
        assert not w.covers(Finding(rule="no-host-callback",
                                    variant="paged@2x2", program="tick",
                                    detail=""))

    def test_apply_waivers_marks_and_filters(self):
        fs = [Finding(rule="r", variant="v", program="tick", detail="a"),
              Finding(rule="r", variant="v", program="mixed", detail="b")]
        live = apply_waivers(fs, [Waiver(rule="r", match="v/tick",
                                         reason="known")])
        assert [f.program for f in live] == ["mixed"]
        assert fs[0].waived and fs[0].waive_reason == "known"
        assert not fs[1].waived

    def test_committed_waiver_file_loads(self):
        # the real committed file must always parse (reasons non-empty)
        load_waivers(os.path.join(REPO, "tools", "audit_waivers.json"))

    def test_report_json_roundtrip(self):
        r = AuditReport(variants=["v"], programs_audited=3,
                        rules_run=["r"],
                        findings=[Finding(rule="r", variant="v",
                                          program="p", detail="d")])
        doc = json.loads(r.to_json())
        assert doc["n_failures"] == 1
        assert doc["findings"][0]["rule"] == "r"


# ---------------------------------------------------------------------------
# live audits (trace-only, single device)
# ---------------------------------------------------------------------------


class TestLiveAudit:
    def test_bucketed_variant_audits_clean(self):
        from repro.analysis.audit import audit_variant
        from repro.analysis.programs import Variant
        report = AuditReport()
        audit_variant(Variant("bucketed", False, None), report,
                      with_budgets=False)
        # 2 prefill buckets + write + tick
        assert report.programs_audited == 4
        assert not report.findings

    def test_recompile_audit_clean(self):
        from repro.analysis.recompile import run_recompile_audit
        findings, census = run_recompile_audit()
        assert not findings, findings
        assert census["prefill"] == 2 and census["chunk"] == 1


class TestShardedAudit:
    """2x2-mesh audit in a subprocess (forced host devices must never be
    set in the main pytest process — same rule as tests/test_distributed)."""

    def test_sharded_variant_audits_clean(self):
        body = textwrap.dedent("""
        import os
        os.environ['XLA_FLAGS'] = \
            '--xla_force_host_platform_device_count=8'
        from repro.analysis.audit import audit_variant
        from repro.analysis.programs import Variant
        from repro.analysis.report import AuditReport
        r = AuditReport()
        audit_variant(Variant("bucketed", False, "2x2"), r,
                      with_budgets=False)
        assert not r.findings, r.findings
        print("programs:", r.programs_audited)
        """)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run([sys.executable, "-c", body],
                             capture_output=True, text=True, timeout=560,
                             env=env, cwd=REPO)
        assert out.returncode == 0, \
            f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
        assert "programs: 4" in out.stdout
