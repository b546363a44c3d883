"""Device time of deadlock detection and the grant rule per engine loop
iteration, in us: the ops under ``stage_deadlock_walk`` and
``stage_ticket_grant``."""

from bench import program_trace

SCOPES = ("stage_deadlock_walk", "stage_ticket_grant")


def read(obs):
    return program_trace.stage_us_per_iter(
        obs, "stage_grant_us_per_iter", SCOPES)
