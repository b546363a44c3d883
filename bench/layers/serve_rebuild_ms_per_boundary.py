"""Host self time of ``repro.serve.rebuild`` per serve boundary, in ms:
each boundary's engine parameters (``_cell_config`` + ``split_config`` of
every lane), and at boundary 0 the initial states."""

from bench import program_trace


def read(obs):
    return program_trace.per_boundary_ms(
        obs, program_trace.span_self(obs, "repro.serve.rebuild"))
