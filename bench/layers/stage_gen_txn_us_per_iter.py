"""Device time of transaction generation per engine loop iteration, in us:
the ops under the ``stage_gen_txn`` and ``stage_ticket_assign`` scopes
(new keys, duplicate/last-use analysis, FIFO same-tick ticket ranking) in
the engine programs, per ``Globals.iters``."""

from bench import program_trace

SCOPES = ("stage_gen_txn", "stage_ticket_assign")


def read(obs):
    return program_trace.stage_us_per_iter(
        obs, "stage_gen_txn_us_per_iter", SCOPES)
