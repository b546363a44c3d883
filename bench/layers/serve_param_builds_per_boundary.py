"""Engine parameter builds of the traced serve call per boundary: the
``repro.engine.split_config`` spans that ``serve`` opens, inside
``repro.serve.rebuild``, when a lane's ``EngineConfig`` is not yet in the
call's memo. One lane under one preset builds once per call, so this reads
1 / boundaries; more means the memo misses. A program without the memo
opens no such span in a serve call, and the metric is left out."""

from bench import program_trace

SPAN = "repro.engine.split_config"


def read(obs):
    pt = program_trace.of(obs)
    n = obs["counters"].get("traced_boundaries", 0)
    if pt is None or SPAN not in pt.span_count or not n:
        return None
    return pt.span_count[SPAN] / n
