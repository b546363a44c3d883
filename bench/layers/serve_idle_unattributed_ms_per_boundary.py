"""Device-idle time of the traced serve call under no program span, per
boundary, in ms: the part of ``serve_host_ms_per_boundary`` that the
``repro.*`` spans leave unexplained."""

from bench import program_trace


def read(obs):
    pt = program_trace.of(obs)
    if pt is None or not any(n.startswith("repro.serve.")
                             for n in pt.span_count):
        return None
    return program_trace.per_boundary_ms(
        obs, pt.idle_split.get(program_trace.NONE, 0.0))
