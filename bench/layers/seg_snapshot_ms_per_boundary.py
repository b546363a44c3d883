"""Device time of the boundary snapshot per serve boundary, in ms: the ops
under the ``seg_snapshot`` scope (``engine._snapshot``, which re-derives
the row aggregates after each segment's loop) in the traced call."""

from bench import program_trace


def read(obs):
    secs = program_trace.stage_seconds(obs, "seg_snapshot_ms_per_boundary")
    if secs is None:
        return None
    return program_trace.per_boundary_ms(obs, secs.get("seg_snapshot", 0.0))
