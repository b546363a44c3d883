"""Stage attribution tests (DESIGN.md §12): every instruction of the
compiled engine programs sits in an ``engine.STAGE_SCOPES`` scope that
``obs.prof.op_stages`` reads back (outermost scope wins), ``run_sim``
marks its host work with ``repro.engine.*`` profiler spans, every stage
ablation of the control seam is a bit-exact no-op under its designated
no-op config, and the compile telemetry counters hold."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.lock import (CostModel, EngineConfig, WorkloadSpec,
                             protocol_params, split_config, init_state_dyn)
from repro.core.lock import engine as E
from repro.obs import compile_log
from repro.obs.prof import STAGE_NOOPS, mixed_fusions, op_stages, stage_map

N_STEPS = 40


def _cfg(proto, *, txn_len=4, write_ratio=1.0, kind="hotspot_update",
         threads=8, rows=64):
    wl = WorkloadSpec(kind=kind, txn_len=txn_len, n_rows=rows,
                      write_ratio=write_ratio)
    return EngineConfig(protocol=protocol_params(proto), costs=CostModel(),
                        workload=wl, n_threads=threads, horizon=500_000)


def _run_steps(stat, dp, ablate=frozenset()):
    step = jax.jit(E._make_step(stat, dp, ablate=ablate))
    st = init_state_dyn(stat, dp)
    for _ in range(N_STEPS):
        st = step(st)
    return st


def _leaf_diffs(a, b):
    pa, _ = jax.tree_util.tree_flatten_with_path(a)
    pb, _ = jax.tree_util.tree_flatten_with_path(b)
    return [jax.tree_util.keystr(k)
            for (k, x), (_, y) in zip(pa, pb) if not jnp.array_equal(x, y)]


# (stage, config under which its ablation must be the identity)
NOOP_CASES = [
    ("dup_analysis", _cfg("mysql", txn_len=1)),
    ("deadlock_walk", _cfg("brook2pl")),
    ("ticket_grant", _cfg("mysql", kind="uniform", write_ratio=0.0)),
    ("commit_cursor", _cfg("mysql", kind="uniform", write_ratio=0.0)),
    ("group_hotspot", _cfg("mysql")),
    ("group_hotspot", _cfg("brook2pl")),
]


@pytest.mark.parametrize("stage,cfg", NOOP_CASES,
                         ids=[f"{s}-{c.protocol.name}-{c.workload.kind}"
                              f"L{c.workload.txn_len}w{c.workload.write_ratio}"
                              for s, c in NOOP_CASES])
def test_ablation_bit_exact_under_noop_config(stage, cfg):
    stat, dp = split_config(cfg)
    full = _run_steps(stat, dp)
    abl = _run_steps(stat, dp, ablate=frozenset({stage}))
    assert _leaf_diffs(full, abl) == []


def test_tick_charge_ablation_touches_only_tb():
    # under ANY config: the breakdown accumulator is write-only state
    cfg = _cfg("mysql")
    stat, dp = split_config(cfg)
    full = _run_steps(stat, dp)
    abl = _run_steps(stat, dp, ablate=frozenset({"tick_charge"}))
    diffs = _leaf_diffs(full, abl)
    assert diffs == [".g.tb"]
    # and something was actually charged — the ablation removed real work
    assert int(full.g.tb.sum()) > 0
    assert int(abl.g.tb.sum()) == 0


def test_empty_ablation_is_production_step():
    cfg = _cfg("group")
    stat, dp = split_config(cfg)
    full = _run_steps(stat, dp)
    default = _run_steps(stat, dp, ablate=frozenset())
    assert _leaf_diffs(full, default) == []


def test_unknown_stage_rejected():
    cfg = _cfg("mysql")
    stat, dp = split_config(cfg)
    with pytest.raises(AssertionError):
        E._make_step(stat, dp, ablate=frozenset({"nonsense"}))
    # a scope label is not an ablation stage
    with pytest.raises(AssertionError):
        E._make_step(stat, dp, ablate=frozenset({"stage_derive"}))


def test_stage_noops_cover_prof_stages():
    assert set(STAGE_NOOPS) == set(E.PROF_STAGES)
    tested = {s for s, _ in NOOP_CASES} | {"tick_charge"}
    assert tested == set(E.PROF_STAGES)


def test_compile_telemetry_counts_fresh_compiles():
    t0 = compile_log.snapshot()

    @jax.jit
    def probe(x):
        return jnp.cumsum(x * 3.0)

    probe(jnp.arange(101.0)).block_until_ready()
    d = compile_log.delta(t0)
    assert d["backend_compiles"] >= 1
    assert d["compile_time_s"] > 0.0
    assert any("probe" in name for name in d["fns"])
    # hlo size of an AOT executable is non-trivial
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    assert compile_log.hlo_module_bytes(compiled) > 100


def test_strict_mode_names_unregistered_entry_points():
    @jax.jit
    def sneaky(x):
        return x * 2 + 1

    sneaky(jnp.arange(7)).block_until_ready()
    mod = sneaky.__wrapped__.__module__
    found = compile_log.unregistered_compiles(prefixes=(mod,))
    assert any("sneaky" in name for name in found)
    # registered entry points are never reported
    compile_log.register(sneaky)
    try:
        assert not any("sneaky" in n
                       for n in compile_log.unregistered_compiles(
                           prefixes=(mod,)))
    finally:
        compile_log._EXTRA.remove(sneaky)


# --- stage scopes in the compiled programs --------------------------------

HLO = """\
HloModule jit_f, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/stage_derive/add"}
  ROOT %mul.2 = s32[8]{0} multiply(%add.1, %add.1), metadata={op_name="jit(f)/while/body/stage_advance/mul"}
}

%region_0.5 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %lt.3 = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.9 (x.1: s32[8]) -> s32[8] {
  %x.1 = s32[8]{0} parameter(0)
  %fusion.7 = s32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/stage_derive/add"}
  %copy.3 = s32[8]{0} copy(%fusion.7)
  ROOT %reduce.4 = s32[8]{0} add(%copy.3, %copy.3), metadata={op_name="jit(f)/seg_snapshot/stage_derive/reduce_max"}
}
"""


def test_op_stages_outermost_scope_and_own_metadata():
    got = op_stages(HLO)
    assert got["fusion.7"] == "stage_derive"        # its own metadata
    assert got["add.1"] == "stage_derive"
    assert got["mul.2"] == "stage_advance"
    assert got["reduce.4"] == "seg_snapshot"        # outer scope wins
    assert got["copy.3"] is None                    # XLA's own: no metadata
    assert got["lt.3"] is None
    assert mixed_fusions(HLO) == {
        "fusion.7": ("stage_advance", "stage_derive")}


def test_stage_scopes_are_unique_and_cover_the_ablation_stages():
    assert len(set(E.STAGE_SCOPES)) == len(E.STAGE_SCOPES)
    assert "seg_snapshot" in E.STAGE_SCOPES
    assert {"stage_derive", "stage_deadlock_walk", "stage_ticket_grant",
            "stage_group_lock", "stage_group_commit", "stage_tick_charge",
            "stage_gen_txn", "stage_ticket_assign",
            "stage_hotspot_detect"} <= set(E.STAGE_SCOPES)


# instructions the executable runs as operations of their own: those of
# fused computations and of applied ones (comparators, combiners) run
# inside their caller
_APPLIED = re.compile(r"(?:to_apply|calls)=%([^\s,]+)")
_BOOKKEEPING = {"parameter", "constant", "tuple", "get-tuple-element",
                "bitcast"}


def _executed(text: str) -> list:
    from repro.obs.prof import _instructions
    applied = set(_APPLIED.findall(text))
    return [(name, op_name) for comp, name, opcode, op_name, _
            in _instructions(text)
            if comp not in applied and opcode not in _BOOKKEEPING]


COVER_CASES = [(p, entry) for p in ("group", "mysql", "brook2pl")
               for entry in ("_run_dyn", "_run_seg_dyn")]


@pytest.mark.parametrize("proto,entry", COVER_CASES,
                         ids=[f"{p}-{e}" for p, e in COVER_CASES])
def test_engine_programs_are_stage_scoped(proto, entry):
    kind, L = ("hotspot_update", 1) if proto == "group" else ("zipf", 4)
    cfg = _cfg(proto, txn_len=L, kind=kind, threads=64, rows=4096)
    stat, dp = split_config(cfg)
    args = [stat, dp, init_state_dyn(stat, dp)]
    if entry == "_run_seg_dyn":
        args.append(jnp.asarray(5_000, jnp.int32))
    jitted = getattr(E, entry)
    if proto == "group" and entry == "_run_dyn":
        stages = stage_map(jitted, *args)
        text = jitted.lower(*args).compile().as_text()
        assert stages == op_stages(text)
    else:
        text = jitted.lower(*args).compile().as_text()
        stages = op_stages(text)
    assert set(stages.values()) - {None} <= set(E.STAGE_SCOPES)
    # every instruction JAX emitted carries a stage; what stays unscoped
    # is XLA's own (copies, no metadata) and the while instruction itself
    emitted = [(n, o) for n, o in _executed(text) if o is not None]
    unscoped = [n for n, _ in emitted if stages[n] is None]
    assert len(unscoped) <= 0.05 * len(emitted), unscoped
    snap = [n for n, o in emitted if "/seg_snapshot/" in o]
    if entry == "_run_seg_dyn":
        assert snap and all(stages[n] == "seg_snapshot" for n in snap)
        # _snapshot's _derive is charged to the snapshot, not the step
        assert any("/seg_snapshot/stage_derive/" in o for _, o in emitted)
    else:
        assert not snap and "seg_snapshot" not in stages.values()
    body = {stages[n] for n, o in emitted if "/while/body/" in o}
    assert {"stage_derive", "stage_tick_charge", "stage_complete",
            "stage_advance"} <= body
    assert "loop_cond" in stages.values()


def test_run_sim_marks_its_host_work(tmp_path):
    import dataclasses
    from jax.profiler import ProfileData
    cfg = dataclasses.replace(_cfg("mysql"), horizon=2_000)
    E.run_sim(cfg).g.now.block_until_ready()        # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        E.run_sim(cfg).g.now.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = [e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events
             if e.name.startswith("repro.")]
    assert names == ["repro.engine.split_config", "repro.engine.init_state",
                     "repro.engine.run"]
