"""Device time of the engine-loop programs per loop iteration, in us.

The programs are the XLA modules whose names contain one of the engine's
jitted entry points (``_run_dyn``, ``_run_seg_dyn``, ``_run_batch``,
``_run_seg_batch``); the iterations are ``Globals.iters`` summed over the
traced calls (lane-iterations for vmapped lanes)."""

from bench.calls import ENGINE_ENTRIES


def read(obs):
    tr, iters = obs["trace"], obs["counters"].get("traced_iters", 0)
    if tr is None or not iters:
        return None
    secs = sum(t for name, t in tr.modules.items()
               if any(p in name for p in ENGINE_ENTRIES))
    if secs <= 0:
        return None
    return 1e6 * secs / iters
