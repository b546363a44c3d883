"""Engine stages of a compiled program, read from its HLO metadata.

Every instruction of an engine program sits under one of the
``jax.named_scope`` labels in ``engine.STAGE_SCOPES`` (DESIGN.md §12.1),
and XLA keeps the label in the instruction's ``op_name`` metadata through
optimisation and fusion. A device trace names each operation it timed by
its HLO instruction (``fusion.341``) and nothing else, so
:func:`stage_map` of the executable that ran turns per-operation device
time into per-stage device time. The benchmark's per-stage metrics read
it (``bench/program_trace.py``).

Scopes can nest (``seg_snapshot`` calls ``_derive``, which the loop body
runs under ``stage_derive``); the outermost stage scope of an
instruction's ``op_name`` wins. A fusion is charged by its own metadata;
:func:`mixed_fusions` names the fusions whose fused computation holds
instructions of more than one stage, which bounds how precise the
attribution is.

``STAGE_NOOPS`` documents the engine's ablation seam
(``engine.PROF_STAGES``), which the benchmark's control builds on: for
each stage, the configuration under which switching it off is a
bit-exact no-op (asserted in tests/test_prof.py).
"""
from __future__ import annotations

import re

from repro.core.lock.engine import PROF_STAGES, STAGE_SCOPES

# Human-readable note per stage: the config under which its ablation is a
# bit-exact no-op (the parity contract tests/test_prof.py asserts), and
# what compute it removes. Keys == engine.PROF_STAGES.
STAGE_NOOPS = {
    "dup_analysis": "exact at txn_len == 1; removes the (T,L,L) pairwise "
                    "dup/last-use scan in gen_txn_dyn",
    "deadlock_walk": "exact when has_detection is off (o2/brook2pl); "
                     "removes the 8-hop waits-for cycle walk",
    "ticket_grant": "exact on a read-only workload (write_ratio=0); "
                    "removes grant masks + FIFO ticket argsort",
    "commit_cursor": "exact on a read-only workload; removes the T*L->R "
                     "segment reductions in _derive",
    "group_hotspot": "exact for protocols without group/hot flags "
                     "(mysql/brook2pl); removes the three lax.cond "
                     "branches",
    "tick_charge": "exact on all state except the write-only tb "
                   "accumulator; removes the TickBreakdown scatters",
}
assert set(STAGE_NOOPS) == set(PROF_STAGES)

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,]+)")
_SCOPES = frozenset(STAGE_SCOPES)


def _stage(op_name: str) -> str | None:
    """The outermost stage scope in an ``op_name`` path, or None."""
    for part in op_name.split("/"):
        if part in _SCOPES:
            return part
    return None


def _instructions(hlo_text: str):
    """Yield (computation, instruction, opcode, op_name | None, calls |
    None) for every instruction of an HLO module's text."""
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        meta = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        yield (comp, name, op.group(1) if op else "",
               meta.group(1) if meta else None,
               calls.group(1) if calls else None)


def op_stages(hlo_text: str) -> dict:
    """``{instruction: stage}`` of every instruction in an HLO module's
    text: the outermost ``engine.STAGE_SCOPES`` label of its ``op_name``,
    or None where it has none (XLA's own copies and tuples, ops outside
    any engine scope)."""
    return {name: _stage(op_name) if op_name else None
            for _, name, _, op_name, _ in _instructions(hlo_text)}


def mixed_fusions(hlo_text: str) -> dict:
    """``{fusion: stages}`` of the fusions whose fused computation holds
    instructions of more than one stage (None counts as a stage: code in
    no scope). Instructions without metadata are left out."""
    by_comp: dict = {}
    fusions = []
    for comp, name, opcode, op_name, calls in _instructions(hlo_text):
        if op_name is not None:
            by_comp.setdefault(comp, set()).add(_stage(op_name))
        if opcode == "fusion" and calls:
            fusions.append((name, calls))
    out = {}
    for name, calls in fusions:
        stages = by_comp.get(calls, set())
        if len(stages) > 1:
            out[name] = tuple(sorted(stages, key=lambda s: s or ""))
    return out


def stage_map(jitted, *args) -> dict:
    """:func:`op_stages` of the executable ``jitted`` runs on ``args``.

    Lowers and compiles ``jitted`` for ``args``; with the persistent
    compile cache warm this loads the executable that ran instead of
    compiling one. Call it outside any measured window: it may compile.
    """
    return op_stages(jitted.lower(*args).compile().as_text())
