"""Device time of tick attribution per engine loop iteration, in us: the
ops under ``stage_tick_charge`` (TickBreakdown and per-record scatters)."""

from bench import program_trace

SCOPES = ("stage_tick_charge",)


def read(obs):
    return program_trace.stage_us_per_iter(
        obs, "stage_tick_charge_us_per_iter", SCOPES)
