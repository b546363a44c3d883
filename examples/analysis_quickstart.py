"""Analysis quickstart: lint one entry point for trace leaks, watch the
linter catch a planted leak, and certify a real schedule serializable.

    PYTHONPATH=src python examples/analysis_quickstart.py

What this demonstrates (DESIGN.md §13):

1. ``jaxpr_lint.lint_entry`` — the twice-lowering oracle. The engine's
   scalar entry is built twice with configs differing in EVERY value
   (timeouts, costs, zipf skew, abort rate, ...) at identical shapes;
   byte-identical jaxprs certify that no knob is constant-folded into
   the executable, i.e. one compile really serves every config.
2. The negative control — a wrapper with the exact bug the linter
   exists for (``int(cfg.protocol.wait_timeout)`` folded into a closure
   before the jit boundary). The linter must FAIL it; a linter that
   passes the planted bug measures nothing.
3. ``isolation.certify_run`` — run the traced engine and certify the
   schedule it actually executed: conflict-serializability from the
   write-write dependency graph, strict-2PL hold discipline for mysql,
   and zero dirty reads even with injected aborts.
4. Brook-2PL's chop-piece mode — txn-level ww cycles are the *expected*
   signature of transaction chopping, so the certifier proves
   serializability at piece granularity (mutually exclusive hold
   intervals + ascending-rank acquisition) instead, and reports the
   txn cycles as informational.
5. A synthetically cyclic trace is REJECTED with the concrete cycle.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.analysis import cli as acli
from repro.analysis import isolation, jaxpr_lint
from repro.core.lock import WorkloadSpec
from repro.compile_cache import enable_compile_cache

WL = WorkloadSpec(kind="zipf", n_rows=256, txn_len=4, zipf_s=1.1, seed=1)


def main():
    # 1. lint the engine's scalar entry (run_lint() does all 14; one is
    # enough to show the shape of a finding-free report)
    ep = next(e for e in jaxpr_lint.default_entry_points()
              if e.name == "engine._run_dyn")
    findings = jaxpr_lint.lint_entry(ep)
    print(f"lint {ep.name}: "
          f"{'clean' if not findings else [str(f) for f in findings]}")
    assert not findings

    # 2. the planted leak must be caught
    bad = jaxpr_lint.lint_entry(jaxpr_lint.leaky_entry_point())
    assert any(f.rule in ("value-leak", "static-leak") for f in bad)
    print(f"planted leak: caught as [{bad[-1].rule}]")

    # 3. certify mysql with injected aborts: acyclic ww graph, strict
    # 2PL holds, no dirty edges
    c = isolation.certify_run("mysql", WL, 16, horizon=40_000,
                              p_abort=0.05, seed=1,
                              **acli.TIMEOUT_OVER)
    print("\n" + c.text())
    assert c.ok and c.mode == "txn-ww" and not c.dirty_edges

    # 4. brook2pl certifies at piece granularity; txn-level cycles are
    # the documented chopping signature, not a bug
    cb = isolation.certify_run("brook2pl", WL, 16, horizon=40_000,
                               p_abort=0.05, seed=1)
    print("\n" + cb.text())
    assert cb.ok and cb.mode == "chop-piece" and cb.chop_ww_cycles

    # 5. and the certifier can say no
    bad_cert = isolation.certify(acli.cyclic_events(), "mysql")
    print(f"\nsynthetic cycle: serializable={bad_cert.serializable} "
          f"cycle={bad_cert.cycle}")
    assert not bad_cert.ok and bad_cert.cycle is not None

    print("\nanalysis quickstart: all checks passed")


if __name__ == "__main__":
    enable_compile_cache()
    main()
