"""With the timed path broken underneath, ``correct`` comes out false.

Each fault is planted in the program (patched for the test), and the rest
of a run is driven as the command drives it, past the look for a chip:

* a step that returns its state unchanged;
* half of the batch left out: half of the clients (closed loop), or half
  of the pool's slots never credited with the requests dispatched to them
  (served loop);
* an answer altered where it is produced: one committed row counter.

The cells run on one chip, so there is no exchange between chips to leave
out. The control (``bench/control.py``, a stage of the engine switched
off through the program's own seam) must fail too.
"""
import time

import jax.numpy as jnp
import pytest

from bench import control, run

CELLS = ["hotspot-group-closed", "zipf-mysql-serve"]


def _run(cell):
    return run.run_cell(cell, seed=424242, seconds=0.0, trace=False,
                        t_start=time.perf_counter())


def _entry(fn):
    """A stand-in for a jitted entry point (the runners count its cache)."""
    fn._cache_size = lambda: 0
    return fn


def _unchanged(mp, engine, runner):
    mp.setattr(engine, "_run_dyn", _entry(lambda stat, dp, s0: s0))
    mp.setattr(engine, "_run_seg_dyn", _entry(lambda stat, dp, s0, until: (
        s0, engine._snapshot(stat, dp, s0))))


def _half_batch(mp, engine, runner):
    split = engine.split_config

    def half_clients(cfg, *a, **k):
        stat, dp = split(cfg, *a, **k)
        return stat, dp._replace(n_active=jnp.asarray(cfg.n_threads // 2,
                                                      jnp.int32))
    mp.setattr(engine, "split_config", half_clients)
    caps = runner._Lane.cap_vector

    def half_requests(self, pad_t):
        v = caps(self, pad_t)           # odd slots never get new credit
        return v.at[1::2].set(jnp.asarray(self.txn[1::2], jnp.int32))
    mp.setattr(runner._Lane, "cap_vector", half_requests)


def _altered(mp, engine, runner):
    def bump(s):
        rows = s.rows._replace(committed_val=s.rows.committed_val.at[1].add(1))
        return s._replace(rows=rows)
    dyn, seg = engine._run_dyn, engine._run_seg_dyn
    mp.setattr(engine, "_run_dyn", _entry(
        lambda stat, dp, s0: bump(dyn(stat, dp, s0))))

    def seg_bumped(stat, dp, s0, until):
        s, snap = seg(stat, dp, s0, until)
        return bump(s), snap
    mp.setattr(engine, "_run_seg_dyn", _entry(seg_bumped))


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_makes_the_run_incorrect(name, fault, tiny_cell, monkeypatch):
    from repro.core.lock import engine
    from repro.serving import runner
    fault(monkeypatch, engine, runner)
    r = _run(tiny_cell(name))
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, tiny_cell):
    cell = tiny_cell(name)
    with control.ablated(cell.limits["control"]["ablate"]):
        r = _run(cell)
    assert r["correct"] is False, r["checks"]
