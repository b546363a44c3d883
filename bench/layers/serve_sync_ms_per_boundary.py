"""Host self time of ``repro.serve.device_get`` per serve boundary, in ms:
the host blocked on the device for the boundary's globals, per-thread
transaction counters and snapshot."""

from bench import program_trace


def read(obs):
    return program_trace.per_boundary_ms(
        obs, program_trace.span_self(obs, "repro.serve.device_get"))
