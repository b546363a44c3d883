"""The event-by-event reference of the served 2PL pool gives the program's
answers exactly, and a change of event timing that keeps every invariant
comes out not correct."""
import dataclasses
import json
import time

import jax.numpy as jnp
import pytest

from bench import run, served_2pl

CELL = "zipf-mysql-serve"

# (rows, offered requests per tick, boundaries, seed): light load; a
# contended table where waits-for cycles form; an overloaded one
LOADS = {
    "light": (4096, 0.002, 8, 2**31 + 7),
    "contended": (512, 0.01, 8, 7),
    "overloaded": (128, 0.02, 12, 2**31 + 999),
}


def _cell(tiny_cell, load):
    rows, rate, bounds, seed = LOADS[load]
    cell = tiny_cell(CELL)
    cfg = json.loads(json.dumps(cell.config))
    cfg["workload"]["n_rows"] = rows
    return dataclasses.replace(cell, config=cfg, traffic={
        **cell.traffic, "rate_per_tick": rate, "boundaries": bounds}), seed


def _run(cell, seed):
    return run.run_cell(cell, seed=seed, seconds=0.0, trace=False,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("load", sorted(LOADS))
def test_reference_gives_the_programs_answers(load, tiny_cell, monkeypatch):
    from bench.drivers import serve
    cell, seed = _cell(tiny_cell, load)
    seen = []
    answers = serve._answers
    monkeypatch.setattr(serve, "_answers", lambda *a: seen.append(
        answers(*a)) or seen[-1])
    r = _run(cell, seed)
    assert r["checks"]["event_mismatch"]["value"] == 0, r["checks"]
    got = seen[0]
    assert got["commits"] > 0 and len(got["responses"]) > 0
    if load != "light":
        assert got["forced_aborts"] > 0     # deadlock victims rolled back


def _patched_params(monkeypatch, **over):
    from repro.core.lock import engine
    split = engine.split_config

    def changed(cfg, *a, **k):
        stat, dp = split(cfg, *a, **k)
        return stat, dp._replace(**{
            k: jnp.asarray(v, getattr(dp, k).dtype) for k, v in over.items()})
    monkeypatch.setattr(engine, "split_config", changed)


@pytest.mark.parametrize("over", [{"dd_coeff": 0.0}, {"lock_base": 4},
                                  {"backoff": 100}],
                         ids=["no_detection_charge", "cheaper_lock",
                              "shorter_backoff"])
def test_timing_change_is_not_correct(over, tiny_cell, monkeypatch):
    cell, seed = _cell(tiny_cell, "contended")
    _patched_params(monkeypatch, **over)
    r = _run(cell, seed)
    assert r["checks"]["event_mismatch"]["value"] > 0, r["checks"]
    assert r["correct"] is False


def test_padded_threads_follow_the_pool():
    assert [served_2pl.padded_threads(n) for n in (1, 64, 65, 256)] == \
        [64, 64, 128, 256]
