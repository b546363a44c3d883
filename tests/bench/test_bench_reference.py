"""The benchmark's copies agree with the program's originals, and the
reference replay reads 0 on a sound run and more on a broken one."""
import dataclasses

import numpy as np
import pytest

from bench import arrivals, reference, stats


@pytest.mark.parametrize("kind,L,seed,hot_base", [
    ("hotspot_update", 1, 5, 17),
    ("hotspot_update", 3, 2**31 - 1, 4000),
    ("zipf", 4, 0, 0),
    ("zipf", 4, 1_234_567_891, 0),
])
def test_keys_match_the_program(kind, L, seed, hot_base):
    import jax.numpy as jnp
    from repro.core.lock import WorkloadSpec, dyn_workload
    from repro.core.lock.workload import gen_txn_dyn
    R = 4096
    spec = WorkloadSpec(kind=kind, n_rows=R, txn_len=L, zipf_s=0.99,
                        seed=seed, hot_base=hot_base)
    tids = np.arange(64)
    ctrs = (tids * 7) % 13
    keys, iswr, dup, _, _ = gen_txn_dyn(
        kind, R, L, dyn_workload(spec), jnp.asarray(tids, jnp.int32),
        jnp.asarray(ctrs, jnp.int32))
    wl = {"kind": kind, "n_rows": R, "txn_len": L, "zipf_s": 0.99}
    rk, rw = reference.txn_keys(wl, seed, hot_base, tids, ctrs)
    np.testing.assert_array_equal(rk, np.asarray(keys))
    np.testing.assert_array_equal(rw, np.asarray(iswr))
    np.testing.assert_array_equal(reference.effective_writes(rk, rw),
                                  np.asarray(iswr) & ~np.asarray(dup))


def test_zipf_table_matches_the_program():
    from repro.core.lock.workload import zipf_cdf
    np.testing.assert_array_equal(reference.zipf_cdf(10_000, 0.99),
                                  zipf_cdf(10_000, 0.99))


def test_chain_matches_the_analytic_oracle():
    from repro.core.lock import CostModel
    from repro.core.lock.metrics import TICKS_PER_SEC
    from repro.core.lock.ref_engine import predicted_tps
    c = CostModel()
    costs = dataclasses.asdict(c)
    for T in (2, 64, 1024):
        want = TICKS_PER_SEC / predicted_tps("group", T, c)
        assert reference.chain_ticks("group", T, costs) == \
            pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("proto", sorted(reference.PROTOCOL))
def test_protocol_constants_match_the_program(proto):
    import dataclasses as dc
    from repro.core.lock.costs import protocol_params
    have = dc.asdict(protocol_params(proto))
    for k, v in reference.PROTOCOL[proto].items():
        assert have[k] == v, k


def test_poisson_copy_matches_the_program():
    from repro.serving.arrivals import poisson
    for rate, horizon, seed in ((0.001, 500_000, 3), (0.02, 40_000, 2**31)):
        np.testing.assert_array_equal(arrivals.poisson(rate, horizon, seed),
                                      poisson(rate, horizon, seed=seed).times)


def test_percentile_is_numpys_linear():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 300):
        v = rng.exponential(size=n)
        for q in (0, 50, 95, 99, 100):
            assert stats.percentile(v, q) == pytest.approx(
                float(np.percentile(v, q)), rel=1e-12)


def test_spread_uses_statistics_quartiles():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def _sound_call():
    """One small group run on the CPU, as the check sees it."""
    import jax
    from repro.core.lock import WorkloadSpec, simulate
    from bench import calls
    spec = WorkloadSpec(kind="zipf", n_rows=4096, txn_len=4, zipf_s=0.99,
                        seed=99)
    s = simulate("mysql", spec, 64, horizon=30_000)
    jax.block_until_ready(s)
    wl = {"kind": "zipf", "n_rows": 4096, "txn_len": 4, "zipf_s": 0.99}
    return wl, calls.to_host(calls.keep_final(s, 99, 0))


def test_numbers_read_zero_on_a_sound_run_and_catch_faults():
    wl, call = _sound_call()
    assert call.commits > 0
    assert reference.numbers(wl, call) == {
        "row_mismatch": 0, "inflight_mismatch": 0, "tick_gap": 0,
        "ledger_gap": 0}
    lost = dataclasses.replace(call, committed_val=call.committed_val.copy())
    r = int(np.flatnonzero(lost.committed_val)[0])
    lost.committed_val[r] -= 1
    assert reference.numbers(wl, lost)["row_mismatch"] == 1
    extra = dataclasses.replace(call, txn=call.txn.copy())
    extra.txn[0] += 1
    n = reference.numbers(wl, extra)
    assert n["row_mismatch"] >= 1 and n["ledger_gap"] == 1
    skew = dataclasses.replace(call, tb=call.tb.copy())
    skew.tb.flat[0] += 5
    assert reference.numbers(wl, skew)["tick_gap"] == 5
