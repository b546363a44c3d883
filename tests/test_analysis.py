"""Tests for the correctness analysis subsystem (repro.analysis).

Positive direction: the lint passes clean on every registered entry
point, and the certifier proves every protocol's discipline on real
traces across seeds and workload kinds. Negative direction — the part
that makes the positive direction mean something — the planted leak
FAILS the lint, and cyclic / corrupted traces are REJECTED.
"""
import numpy as np
import pytest

from repro.analysis import cli as acli
from repro.analysis import isolation as ISO
from repro.analysis import jaxpr_lint as JL
from repro.core.lock.costs import PROTOCOLS, protocol_params
from repro.core.lock.workload import WorkloadSpec
from repro.obs.trace import simulate_traced

W_ZIPF = WorkloadSpec(kind="zipf", n_rows=256, txn_len=4, zipf_s=1.1)
TIMEOUTS = dict(wait_timeout=8_000, commit_wait_timeout=8_000)


def _over(proto: str) -> dict:
    # brook2pl's timeout=0 IS the protocol; everyone else gets short
    # timeouts so detection-free deadlocks resolve inside the horizon
    return {} if proto == "brook2pl" else dict(TIMEOUTS)


# ---------------------------------------------------------------------------
# jaxpr lint
# ---------------------------------------------------------------------------

class TestLint:
    def test_all_registered_entry_points_clean(self):
        """The repo invariant, enforced: every entry point lowers to the
        byte-identical jaxpr across value-only config variants and
        passes every rule walk."""
        rep = JL.run_lint()
        assert rep.ok, rep.text()
        assert len(rep.entries) >= 12

    def test_registry_covers_compile_log_entries(self):
        """The lint registry mirrors compile_log._jitted(): every
        module-level registered jit is linted (instance-level _EXTRA
        registrations are runtime-scoped and exempt)."""
        names = {ep.name for ep in JL.default_entry_points()}
        for want in ("engine._run_dyn", "engine._run_batch",
                     "engine._run_seg_dyn", "engine._run_seg_batch",
                     "aria._run_dyn", "aria._run_batch",
                     "aria._run_seg_dyn", "aria._run_seg_batch",
                     "trace._run_traced", "serving._hist_add",
                     "kernels.grouped_scatter_apply",
                     "kernels.segment_sums"):
            assert want in names, f"{want} missing from lint registry"

    def test_leaky_entry_point_fails(self):
        """Negative control: a wrapper that Python-folds wait_timeout
        before the jit boundary must produce a leak finding naming the
        failure mode."""
        findings = JL.lint_entry(JL.leaky_entry_point())
        assert any(f.rule in ("value-leak", "static-leak")
                   for f in findings), findings

    def test_concretized_knob_fails_loudly(self):
        """int(traced) inside the entry raises at lowering; the lint
        reports it as a finding instead of crashing."""
        import jax.numpy as jnp
        from repro.core.lock import engine as E

        def build(v):
            stat, dp = JL._split(v)
            return (stat,), (dp, E.init_state_dyn(stat, dp))

        def concretizing(stat, dp, s0):
            wt = int(dp.wait_timeout)           # ConcretizationTypeError
            return E._run_dyn.__wrapped__(
                stat, dp._replace(wait_timeout=jnp.asarray(wt)), s0)

        ep = JL.EntryPoint("negative.concretizing", concretizing, build)
        findings = JL.lint_entry(ep)
        assert any(f.rule == "concretized" for f in findings), findings

    def test_cond_count_rule_fires_on_mismatch(self):
        """Pinning a wrong expected count produces a cond-count finding
        that names the registry sites — the tripwire for a protocol
        branch getting folded or forked."""
        base = next(ep for ep in JL.default_entry_points()
                    if ep.name == "engine._run_dyn")
        wrong = JL.EntryPoint(base.name, base.fn, base.build,
                              cond_count=base.cond_count + 1)
        findings = JL.lint_entry(wrong)
        assert any(f.rule == "cond-count" for f in findings), findings

    def test_cond_sites_match_protocol_registry(self):
        """Every cond site is gated by a real traced flag — either a
        ProtocolParams field or a DynParams run knob (contention_attrib
        is gated by EngineConfig.attrib)."""
        pp = protocol_params("mysql")
        for site, flag in JL.PROTOCOL_COND_SITES.items():
            assert hasattr(pp, flag) or flag in JL.E.DynParams._fields, \
                (site, flag)


# ---------------------------------------------------------------------------
# serializability certifier
# ---------------------------------------------------------------------------

class TestCertifier:
    @pytest.mark.parametrize("proto", PROTOCOLS)
    def test_matrix_certifies(self, proto):
        """6 protocols x 3 seeds x 3 workload kinds, with injected
        aborts: every run certifies under its protocol's discipline and
        no run has a dirty edge."""
        saw_commits = saw_aborts = 0
        for kind in acli.KINDS:
            for seed in acli.SEEDS:
                c = ISO.certify_run(
                    proto, acli._workload(kind, seed), acli.THREADS,
                    horizon=acli.HORIZON, p_abort=0.05, seed=seed,
                    **_over(proto))
                assert c.ok, c.text()
                assert not c.dirty_edges
                saw_commits += c.n_committed
                saw_aborts += c.n_aborted
        # the matrix must actually exercise both terminators
        assert saw_commits > 0
        assert saw_aborts > 0

    def test_abort_event_counts_match_engine(self):
        """The new abort trace event fires exactly once per rollback:
        event count == user_aborts + forced_aborts."""
        s, tb = simulate_traced("mysql", W_ZIPF, 16, horizon=40_000,
                                p_abort=0.1, seed=2, cap=65_536,
                                **TIMEOUTS)
        from repro.obs.trace import EV_ABORT, events_host
        ev = events_host(tb)
        n_abort_ev = int((ev["ev"] == EV_ABORT).sum())
        assert n_abort_ev == int(s.g.user_aborts) + int(s.g.forced_aborts)
        assert n_abort_ev > 0

    def test_brook2pl_chop_mode(self):
        """brook2pl certifies in chop-piece mode: txn-level ww cycles
        are present (the chopping signature) while hold intervals stay
        mutually exclusive and ranks ascend."""
        c = ISO.certify_run("brook2pl", W_ZIPF, 16, horizon=40_000,
                            p_abort=0.05, seed=1)
        assert c.mode == "chop-piece"
        assert c.ok, c.text()
        assert c.chop_ww_cycles     # expected, informational
        # strict protocols never report chop cycles
        c2 = ISO.certify_run("mysql", W_ZIPF, 16, horizon=40_000,
                             seed=1, **TIMEOUTS)
        assert c2.mode == "txn-ww" and not c2.chop_ww_cycles

    def test_brook_rank_check_rejects_descending(self):
        """A synthetic attempt requesting rows against the rank order is
        flagged (the discipline check is live, not vacuous)."""
        from repro.obs.trace import EV_COMMIT, EV_GRANT, EV_WAIT_ENTER
        ev = [(0, 0, 5, EV_WAIT_ENTER), (1, 0, 5, EV_GRANT),
              (2, 0, 2, EV_WAIT_ENTER), (3, 0, 2, EV_GRANT),
              (9, 0, -1, EV_COMMIT)]
        events = {"ts": np.array([e[0] for e in ev]),
                  "tid": np.array([e[1] for e in ev]),
                  "row": np.array([e[2] for e in ev]),
                  "ev": np.array([e[3] for e in ev]),
                  "n": len(ev), "dropped": 0, "cap": len(ev)}
        rank = list(range(8))       # rank == row id; 5 -> 2 descends
        c = ISO.certify(events, protocol_params("brook2pl"),
                        acq_rank=rank)
        assert any("brook-rank" in v for v in c.violations), c.text()

    def test_cyclic_trace_rejected(self):
        c = ISO.certify(acli.cyclic_events(), "mysql")
        assert not c.serializable
        assert c.cycle is not None
        assert not c.ok

    def test_corrupted_trace_rejected(self):
        c = ISO.certify(acli.corrupted_events(), "mysql")
        assert not c.ok
        assert any("input-invalid" in v for v in c.violations)

    def test_dropped_trace_is_lower_bound(self):
        """A capacity-truncated trace still certifies its prefix but
        says so."""
        _s, tb = simulate_traced("mysql", W_ZIPF, 16, horizon=40_000,
                                 seed=1, cap=64, **TIMEOUTS)
        assert int(tb.dropped) > 0
        c = ISO.certify(tb, "mysql")
        assert c.lower_bound

    def test_selftest_passes(self):
        assert acli.run_selftest(verbose=False) == []


# ---------------------------------------------------------------------------
# satellite: trace-vs-breakdown wait accounting property
# ---------------------------------------------------------------------------

def _wait_bound_holds(proto, kind, p_abort, seed) -> tuple:
    W = WorkloadSpec(kind=kind, n_rows=256, txn_len=4, zipf_s=1.1,
                     n_warehouses=4, seed=seed)
    s, tb = simulate_traced(proto, W, 16, horizon=40_000,
                            p_abort=p_abort, seed=seed, cap=65_536,
                            **_over(proto))
    trace_wait = ISO.total_trace_wait_ticks(tb)
    tb_lock_wait = int(np.asarray(s.g.tb, dtype=np.int64)[:, 1].sum())
    return trace_wait, tb_lock_wait


class TestWaitAccountingProperty:
    """Trace-derived wait spans can never exceed what the engine charged
    to the lock_wait TickBreakdown bin (cold+hot): every resolved span
    covers ticks the thread provably spent in a wait phase, and
    unresolved/dropped spans only shrink the trace side."""

    @pytest.mark.parametrize("proto,kind,p_abort", [
        ("mysql", "zipf", 0.0), ("mysql", "tpcc", 0.1),
        ("o1", "hotspot_update", 0.05), ("group", "zipf", 0.05),
        ("bamboo", "tpcc", 0.0), ("brook2pl", "zipf", 0.05),
    ])
    def test_trace_wait_bounded_by_breakdown(self, proto, kind, p_abort):
        trace_wait, tb_lock_wait = _wait_bound_holds(proto, kind,
                                                     p_abort, seed=2)
        assert trace_wait <= tb_lock_wait
        if trace_wait:              # and the bound is not vacuous
            assert tb_lock_wait > 0

    def test_trace_wait_property_fuzzed(self):
        """Hypothesis twin of the parametrized cases (skips where the
        environment lacks hypothesis — the deterministic cases above
        always run)."""
        pytest.importorskip(
            "hypothesis", reason="hypothesis not installed; the "
            "parametrized twin covers the property deterministically")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=10, deadline=None)
        @given(proto=st.sampled_from(PROTOCOLS),
               kind=st.sampled_from(("zipf", "tpcc", "hotspot_update")),
               p_abort=st.sampled_from((0.0, 0.05, 0.1)),
               seed=st.integers(min_value=0, max_value=7))
        def prop(proto, kind, p_abort, seed):
            trace_wait, tb_lock_wait = _wait_bound_holds(proto, kind,
                                                         p_abort, seed)
            assert trace_wait <= tb_lock_wait

        prop()
