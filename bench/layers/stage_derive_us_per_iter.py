"""Device time of ``_derive`` per engine loop iteration, in us: the ops
under the ``stage_derive`` scope, the T*L -> R segment reductions that
rebuild every row's grant and commit cursors."""

from bench import program_trace

SCOPES = ("stage_derive",)


def read(obs):
    return program_trace.stage_us_per_iter(
        obs, "stage_derive_us_per_iter", SCOPES)
