"""Static trace-leak linter: certify that every knob stays traced.

The repo's perf story rests on one invariant (DESIGN.md §3): the compile
key is *shapes only* — every protocol flag, cost constant, workload
parameter, and run knob is a traced jnp leaf, so one executable serves
every config at a shape. Nothing has enforced it statically until now; a
single careless ``float(dp.x)`` in a wrapper silently forks the
executable cache and invalidates every sweep/compaction/serving number.

**The leak oracle is twice-lowering.** Each registered entry point is
built twice from the *config level* (EngineConfig / AriaConfig / raw
arrays) with variant configs that differ in EVERY value-like leaf while
agreeing in every shape, then lowered with ``jax.make_jaxpr``. Because
traced arguments are abstracted to avals, a knob's *value* can reach the
jaxpr text only by leaking:

* a builder folded it into the static part (``StaticShape`` mismatch —
  caught by direct equality before lowering);
* a wrapper closed over a Python scalar computed from the config before
  the jit boundary (a constant in the jaxpr — caught by the byte-diff);
* the traced code concretized it (``int(dp.x)`` / ``if dp.x:`` — raises
  ``ConcretizationTypeError`` at lowering, reported as a finding);
* its dtype/weak-type depends on its value (aval text diff).

Byte-identical jaxprs across the two variants therefore certify "no
knob is constant-folded anywhere on this entry point's build path". On
a mismatch the linter bisects leaf-by-leaf and names the offending leaf
path(s).

**Rule walks** (over the variant-A jaxpr, recursing into cond/while/pjit
sub-jaxprs):

* ``callbacks``  — no host/io/debug callback primitive inside a
  ``while`` body: a host round-trip per tick-loop iteration is a
  100-1000x slowdown and deadlocks under donated buffers.
* ``wide-dtype`` — no 64-bit value (f64/i64/u64/c128) inside a ``while``
  body: an accidental x64 promotion doubles hot-loop bandwidth (the
  engine is memory-bound at AI ~ 0.6, DESIGN.md §12).
* ``weak-float`` — no weakly-typed *float* inside a ``while`` body: a
  Python float literal riding the hot loop is the classic source of
  silent f32->f64 promotion once x64 is enabled. (Weak i32/bool are the
  normal residue of integer literals and stay allowed.)
* ``scatter-mode`` — no scatter-family eqn may resolve to
  ``PROMISE_IN_BOUNDS`` (out-of-bounds writes become UB). Note
  ``mode=None`` resolves to FILL_OR_DROP in the jaxpr, byte-identical
  to an explicit ``mode="drop"`` — "has an explicit mode=" is not
  checkable post-lowering, so the rule checks the resolved semantics
  instead. Gathers are exempt: plain ``x[idx]`` lowers to
  PROMISE_IN_BOUNDS gathers and the engine pre-clips every index.
* ``cond-count`` — the number of ``cond`` primitives matches the
  protocol-branch registry (:data:`PROTOCOL_COND_SITES`): a runtime-
  skippable protocol branch that silently becomes unconditional compute
  (or a new Python-level protocol fork) changes this count.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax._src import core as _jcore
from jax.lax import GatherScatterMode

from repro.core.lock import aria as A
from repro.core.lock import engine as E
from repro.core.lock.costs import (CostModel, PROTOCOLS, protocol_params)
from repro.core.lock.engine import EngineConfig, I32, split_config
from repro.core.lock.workload import WorkloadSpec
from repro.obs import trace as obs_trace

# ---------------------------------------------------------------------------
# cond-site registry: every lax.cond in the engine step, gated by a
# ProtocolParams flag (the PROTOCOLS table in costs.py is the source of
# truth for which flags exist) or a run knob (``contention_attrib`` is
# gated by ``EngineConfig.attrib`` / ``DynParams.attrib``, the per-record
# contention accumulator — DESIGN.md §14).
# ---------------------------------------------------------------------------

PROTOCOL_COND_SITES = {
    "deadlock_walk": "has_detection",
    "group_lock": "group_lock",
    "group_commit": "group_commit",
    "hotspot_detect": "hot_queue",
    "contention_attrib": "attrib",
}

_FORBIDDEN_IN_WHILE = ("pure_callback", "io_callback", "debug_callback",
                       "callback", "outside_call", "host_callback_call")
_WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")
_SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-mul", "scatter-min",
                  "scatter-max", "scatter-apply")


@dataclasses.dataclass(frozen=True)
class LintFinding:
    entry: str
    rule: str
    detail: str

    def __str__(self) -> str:
        return f"{self.entry}: [{self.rule}] {self.detail}"


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One lintable jitted entry point.

    ``build(variant)`` returns ``(static_args, dyn_args)`` built from a
    variant config; variants 0 and 1 must differ in every value-like
    leaf and agree in every shape. ``fn`` is the jitted wrapper (lowered
    via ``__wrapped__`` with the static prefix marked static).
    ``cond_count`` pins the expected number of ``cond`` primitives
    (None = not checked; vmapped entries lower conds to selects).
    """
    name: str
    fn: Callable
    build: Callable[[int], tuple[tuple, tuple]]
    cond_count: int | None = None
    expect_while: bool = True


# ---------------------------------------------------------------------------
# variant config builders — every value-like field differs between variants
# at identical shapes. Bools flip with the variant parity.
# ---------------------------------------------------------------------------

_SHAPE = dict(kind="zipf", n_rows=64, txn_len=2, n_threads=8)


def _flip(i: int) -> bool:
    # lane-wise parity that differs between variant (i, i+2) pairs, so
    # batched builds also flip every bool per lane: 0,1,2,3 -> F,T,T,F
    return bool((i ^ (i >> 1)) & 1)


def _workload(i: int) -> WorkloadSpec:
    return WorkloadSpec(
        kind=_SHAPE["kind"], n_rows=_SHAPE["n_rows"],
        txn_len=_SHAPE["txn_len"],
        write_ratio=0.7 + 0.1 * i, zipf_s=0.6 + 0.1 * i, n_hot=2 + i,
        n_warehouses=1 + i, seed=11 + i, reads_lock=_flip(i),
        hot_base=i)


def _engine_cfg(i: int) -> EngineConfig:
    b = _flip(i)
    proto = protocol_params(
        "mysql",
        lock_base=10 + i, grant_cost=2 + i, dd_coeff=3.0 + 0.25 * i,
        has_detection=not b, hot_queue=b, early_release=b, early_all=b,
        group_lock=b, group_commit=b, dynamic_batch=not b,
        batch_size=8 + i, hot_threshold=16 + i, proactive_abort=b,
        ordered_acquire=b, per_op_release=b,
        wait_timeout=400_000 + i, commit_wait_timeout=300_000 + i)
    costs = CostModel(
        op_exec=50 + i, read_exec=20 + i, commit_base=100 + i,
        sync_lat=10 + i, rb_base=80 + i, rb_per_op=40 + i,
        backoff=200 + i, queue_insert=3 + i, arrival_rate=0.01 * (i + 1),
        rb_turn_timeout=20_000 + i)
    return EngineConfig(
        protocol=proto, costs=costs, workload=_workload(i),
        n_threads=_SHAPE["n_threads"], horizon=10_000 + i,
        p_abort=0.02 * (i + 1), drain=b, max_iters=900_000 + i,
        seed=5 + i, attrib=not b)


def _split(i: int):
    stat, dp = split_config(_engine_cfg(i))
    return stat, dp


def _build_run_dyn(v: int):
    stat, dp = _split(v)
    return (stat,), (dp, E.init_state_dyn(stat, dp))


def _build_run_batch(v: int):
    stat0, dp0 = _split(2 * v)
    stat1, dp1 = _split(2 * v + 1)
    assert stat0 == stat1
    dps = jax.tree.map(lambda a, b: jnp.stack([a, b]), dp0, dp1)
    s0 = E.init_state_dyn(stat0, dp0)
    s0s = jax.tree.map(lambda x: jnp.stack([x, x]), s0)
    return (stat0,), (dps, s0s)


def _build_run_seg_dyn(v: int):
    stat, dp = _split(v)
    return (stat,), (dp, E.init_state_dyn(stat, dp),
                     jnp.asarray(5_000 + v, I32))


def _build_run_seg_batch(v: int):
    (stat,), (dps, s0s) = _build_run_batch(v)
    untils = jnp.asarray([4_000 + v, 6_000 + v], I32)
    return (stat,), (dps, s0s, untils)


def _aria_cfg(i: int) -> A.AriaConfig:
    costs = CostModel(op_exec=50 + i, commit_base=100 + i, sync_lat=5 + i)
    return A.AriaConfig(workload=_workload(i), costs=costs,
                        n_threads=_SHAPE["n_threads"], horizon=10_000 + i)


def _build_aria_dyn(v: int):
    stat, dp = A.split_aria(_aria_cfg(v))
    return (stat,), (dp,)


def _build_aria_batch(v: int):
    s0, d0 = A.split_aria(_aria_cfg(2 * v))
    s1, d1 = A.split_aria(_aria_cfg(2 * v + 1))
    assert s0 == s1
    return (s0,), (jax.tree.map(lambda a, b: jnp.stack([a, b]), d0, d1),)


def _build_aria_seg_dyn(v: int):
    stat, dp = A.split_aria(_aria_cfg(v))
    return (stat,), (dp, A.init_aria_state(stat),
                     jnp.asarray(5_000 + v, I32))


def _build_aria_seg_batch(v: int):
    (stat,), (dps,) = _build_aria_batch(v)
    s0 = A.init_aria_state(stat)
    s0s = jax.tree.map(lambda x: jnp.stack([x, x]), s0)
    return (stat,), (dps, s0s, jnp.asarray([4_000 + v, 6_000 + v], I32))


def _build_traced(v: int):
    stat, dp = _split(v)
    tb = obs_trace.make_trace(cap=32 + v, alloc=64, on=_flip(v))
    return (stat,), (dp, E.init_state_dyn(stat, dp), tb,
                     jnp.asarray(7_000 + v, I32))


def _build_hist_add(v: int):
    from repro.serving import runner as S
    hist = jnp.full((E.N_HIST,), v, I32)
    ticks = jnp.arange(16, dtype=I32) * (v + 1)
    valid = jnp.arange(16) % 2 == (v % 2)
    return (), (hist, ticks, valid)


def _kernel_arrays(v: int, shape):
    n = 1
    for d in shape:
        n *= d
    base = jnp.arange(n, dtype=jnp.float32).reshape(shape)
    return base * (0.01 * (v + 1)) + v


def _build_flash(v: int):
    q = _kernel_arrays(v, (1, 2, 16, 8))
    k = _kernel_arrays(v + 4, (1, 2, 16, 8))
    vv = _kernel_arrays(v + 8, (1, 2, 16, 8))
    return (), (q, k, vv)


def _build_grouped_scatter(v: int):
    table = _kernel_arrays(v, (32, 8))
    ids = (jnp.arange(64, dtype=I32) * (v + 3)) % 32
    updates = _kernel_arrays(v + 2, (64, 8))
    return (), (table, ids, updates)


def _build_segment_sums(v: int):
    seg_ids = (jnp.arange(64, dtype=I32) * (v + 3)) % 16
    updates = _kernel_arrays(v, (64, 8))
    return (), (seg_ids, updates)


def default_entry_points() -> list[EntryPoint]:
    """Every registered jitted entry point (mirrors the compile_log
    registry — keep the two in sync; tested in tests/test_analysis.py)."""
    n_cond = len(PROTOCOL_COND_SITES)
    eps = [
        EntryPoint("engine._run_dyn", E._run_dyn, _build_run_dyn,
                   cond_count=n_cond),
        EntryPoint("engine._run_batch", E._run_batch, _build_run_batch),
        EntryPoint("engine._run_seg_dyn", E._run_seg_dyn,
                   _build_run_seg_dyn, cond_count=n_cond),
        EntryPoint("engine._run_seg_batch", E._run_seg_batch,
                   _build_run_seg_batch),
        EntryPoint("aria._run_dyn", A._run_dyn, _build_aria_dyn),
        EntryPoint("aria._run_batch", A._run_batch, _build_aria_batch),
        EntryPoint("aria._run_seg_dyn", A._run_seg_dyn,
                   _build_aria_seg_dyn),
        EntryPoint("aria._run_seg_batch", A._run_seg_batch,
                   _build_aria_seg_batch),
        EntryPoint("trace._run_traced", obs_trace._run_traced,
                   _build_traced, cond_count=n_cond),
    ]
    from repro.serving import runner as S
    eps.append(EntryPoint("serving._hist_add", S._hist_add,
                          _build_hist_add, expect_while=False))
    try:    # Pallas-backed entry points; optional on exotic hosts
        from repro.kernels.flash_attention import kernel as fk, ops as fo
        from repro.kernels.grouped_scatter import kernel as gk, ops as go

        def _segment_sums_g16(seg_ids, updates):
            # num_groups is a shape argument, fixed like the other shapes
            return gk.segment_sums(seg_ids, updates, 16, interpret=True)

        eps += [
            EntryPoint("kernels.flash_attention", fo.flash_attention,
                       _build_flash, expect_while=False),
            EntryPoint("kernels.flash_attention_bhsd",
                       fk.flash_attention_bhsd, _build_flash,
                       expect_while=False),
            EntryPoint("kernels.grouped_scatter_apply",
                       go.grouped_scatter_apply, _build_grouped_scatter,
                       expect_while=False),
            EntryPoint("kernels.segment_sums", _segment_sums_g16,
                       _build_segment_sums, expect_while=False),
        ]
    except Exception:
        pass
    return eps


# ---------------------------------------------------------------------------
# lowering + diffing
# ---------------------------------------------------------------------------

def _lower(ep: EntryPoint, static: tuple, dyn: tuple):
    fn = getattr(ep.fn, "__wrapped__", ep.fn)
    statics = tuple(range(len(static)))
    return jax.make_jaxpr(fn, static_argnums=statics)(*static, *dyn)


def _text(jaxpr) -> str:
    """Canonical comparable text: jaxpr body PLUS const values.

    A closure-folded knob becomes a ``ClosedJaxpr`` const, which the
    jaxpr body renders as an anonymous constvar — byte-identical across
    variants. The leak lives in the const *value*, so it must be part of
    the compared text (hashed, to keep big tables cheap)."""
    import hashlib
    import numpy as np
    parts = [str(jaxpr)]
    for c in jaxpr.consts:
        a = np.asarray(c)
        parts.append(f"const {a.dtype}{a.shape} "
                     f"{hashlib.sha256(a.tobytes()).hexdigest()}")
    return "\n".join(parts)


def _leaf_paths(args: tuple) -> list[tuple]:
    leaves, _ = jax.tree_util.tree_flatten_with_path(args)
    return [p for p, _ in leaves]


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def _bisect_leak(ep: EntryPoint, static: tuple, a: tuple, b: tuple,
                 base_text: str, limit: int = 8) -> list[str]:
    """Name the leaf path(s) whose value changes the lowered program."""
    la, tda = jax.tree_util.tree_flatten_with_path(a)
    lb, _ = jax.tree_util.tree_flatten_with_path(b)
    offenders = []
    for k, ((path, xa), (_, xb)) in enumerate(zip(la, lb)):
        flat = [x for _, x in la]
        flat[k] = xb
        mixed = jax.tree_util.tree_unflatten(tda, flat)
        try:
            txt = _text(_lower(ep, static, mixed))
        except Exception as e:
            offenders.append(f"{_path_str(path)} (lowering raised "
                             f"{type(e).__name__})")
            continue
        if txt != base_text:
            offenders.append(_path_str(path))
        if len(offenders) >= limit:
            offenders.append("... (bisect stopped)")
            break
    return offenders


# ---------------------------------------------------------------------------
# jaxpr walking + rules
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if isinstance(x, _jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, _jcore.Jaxpr):
                yield x


def _walk(jaxpr, inside_while: bool, visit) -> None:
    for eqn in jaxpr.eqns:
        visit(eqn, inside_while)
        inner = inside_while or eqn.primitive.name == "while"
        for sub in _sub_jaxprs(eqn):
            _walk(sub, inner, visit)


def _rule_findings(ep: EntryPoint, jaxpr) -> list[LintFinding]:
    out: list[LintFinding] = []
    counts = {"cond": 0, "while": 0}

    def visit(eqn, in_while):
        name = eqn.primitive.name
        if name in counts:
            counts[name] += 1
        if in_while:
            if name in _FORBIDDEN_IN_WHILE:
                out.append(LintFinding(ep.name, "callbacks",
                                       f"`{name}` inside a while body — "
                                       f"host round-trip per iteration"))
            for v in eqn.outvars:
                dt = str(getattr(v.aval, "dtype", ""))
                if dt in _WIDE_DTYPES:
                    out.append(LintFinding(
                        ep.name, "wide-dtype",
                        f"`{name}` produces {dt} inside a while body"))
                elif getattr(v.aval, "weak_type", False) and \
                        dt.startswith("float"):
                    out.append(LintFinding(
                        ep.name, "weak-float",
                        f"`{name}` produces weakly-typed {dt} inside a "
                        f"while body (Python float literal in the hot "
                        f"loop?)"))
        if name in _SCATTER_PRIMS:
            if eqn.params.get("mode") == GatherScatterMode.PROMISE_IN_BOUNDS:
                out.append(LintFinding(
                    ep.name, "scatter-mode",
                    f"`{name}` resolves to PROMISE_IN_BOUNDS (OOB "
                    f"writes are UB; use mode='drop'/'fill')"))

    _walk(jaxpr.jaxpr, False, visit)
    if ep.expect_while and counts["while"] == 0:
        out.append(LintFinding(ep.name, "structure",
                               "expected a while loop, found none"))
    if ep.cond_count is not None and counts["cond"] != ep.cond_count:
        sites = ", ".join(f"{k} ({v})"
                          for k, v in PROTOCOL_COND_SITES.items())
        out.append(LintFinding(
            ep.name, "cond-count",
            f"{counts['cond']} cond primitive(s), expected "
            f"{ep.cond_count} — registry sites: {sites}; a protocol "
            f"branch was folded, un-conded, or forked in Python"))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LintReport:
    findings: list[LintFinding]
    entries: list[str]

    @property
    def ok(self) -> bool:
        return not self.findings

    def text(self) -> str:
        lines = [f"# jaxpr lint: {len(self.entries)} entry point(s), "
                 f"{len(self.findings)} finding(s)"]
        for name in self.entries:
            n = sum(1 for f in self.findings if f.entry == name)
            lines.append(f"{'FAIL' if n else 'ok  '} {name}"
                         + (f" ({n} finding(s))" if n else ""))
        lines += [str(f) for f in self.findings]
        lines.append("lint: " + ("PASS" if self.ok else "FAIL"))
        return "\n".join(lines)


def lint_entry(ep: EntryPoint) -> list[LintFinding]:
    """Twice-lower one entry point and run every rule. Never raises on a
    broken entry — lowering failures become findings (a knob concretized
    under trace is exactly the loud variant of the leak)."""
    # Build+lower each variant back to back: a wrapper that closes over
    # config-derived Python scalars binds them at build time, so variant
    # A must be lowered before variant B is built.
    try:
        stat_a, dyn_a = ep.build(0)
    except Exception as e:
        return [LintFinding(ep.name, "build",
                            f"builder raised {type(e).__name__}: {e}")]
    try:
        jx_a = _lower(ep, stat_a, dyn_a)
        txt_a = _text(jx_a)
    except Exception as e:
        return [LintFinding(ep.name, "concretized",
                            f"lowering raised {type(e).__name__}: {e} — "
                            f"a traced leaf was concretized")]
    out = _rule_findings(ep, jx_a)
    try:
        stat_b, dyn_b = ep.build(1)
    except Exception as e:
        out.append(LintFinding(ep.name, "build",
                               f"builder raised {type(e).__name__}: {e}"))
        return out
    if stat_b != stat_a:
        out.append(LintFinding(
            ep.name, "static-leak",
            f"value-like config change moved the static part: "
            f"{stat_a!r} != {stat_b!r}"))
        return out
    try:
        txt_b = _text(_lower(ep, stat_b, dyn_b))
    except Exception as e:
        out.append(LintFinding(ep.name, "concretized",
                               f"variant-B lowering raised "
                               f"{type(e).__name__}: {e}"))
        return out
    if txt_a != txt_b:
        who = _bisect_leak(ep, stat_a, dyn_a, dyn_b, txt_a)
        out.append(LintFinding(
            ep.name, "value-leak",
            "jaxpr differs across value-only config variants — traced "
            "knob constant-folded into the program; offending leaf "
            "path(s): " + (", ".join(who) if who else "(bisect found "
            "none: leak is in a non-leaf closure)")))
    return out


def run_lint(entries: Sequence[EntryPoint] | None = None) -> LintReport:
    eps = list(entries) if entries is not None else default_entry_points()
    findings: list[LintFinding] = []
    for ep in eps:
        findings.extend(lint_entry(ep))
    return LintReport(findings=findings, entries=[ep.name for ep in eps])


# ---------------------------------------------------------------------------
# negative control: a deliberately leaky entry point (CI selftest + tests)
# ---------------------------------------------------------------------------

def leaky_entry_point() -> EntryPoint:
    """An entry point with the exact bug the linter exists for: its
    builder Python-folds ``wait_timeout`` into a closure constant before
    the jit boundary. One compiled program per timeout value — the
    silent executable-cache fork. The linter must FAIL on it."""

    def build(v: int):
        cfg = _engine_cfg(v)
        wt = int(cfg.protocol.wait_timeout)     # BUG: folded eagerly

        def leaky(stat, dp, s0):
            dp = dp._replace(wait_timeout=jnp.asarray(wt, I32))
            return E._run_dyn.__wrapped__(stat, dp, s0)

        stat, dp = split_config(cfg)
        build.fn = leaky            # lowered via ep.fn at call time
        return (stat,), (dp, E.init_state_dyn(stat, dp))

    class _Proxy:
        # resolves to whichever closure build() last produced
        @property
        def __wrapped__(self):
            return build.fn

        def __call__(self, *a, **k):
            return build.fn(*a, **k)

    return EntryPoint("negative.leaky_run_dyn", _Proxy(), build,
                      cond_count=len(PROTOCOL_COND_SITES))
