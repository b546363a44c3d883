"""Device time of the group-locking branches per engine loop iteration, in
us: the ops under ``stage_group_lock``, ``stage_group_commit`` and
``stage_hotspot_detect``."""

from bench import program_trace

SCOPES = ("stage_group_lock", "stage_group_commit", "stage_hotspot_detect")


def read(obs):
    return program_trace.stage_us_per_iter(
        obs, "stage_group_us_per_iter", SCOPES)
