"""Host self time of ``repro.serve.lanes`` per serve boundary, in ms:
retire, admit, dispatch, the conservation check and the boundary's
records, for every lane."""

from bench import program_trace


def read(obs):
    return program_trace.per_boundary_ms(
        obs, program_trace.span_self(obs, "repro.serve.lanes"))
