"""Programs compiled or loaded inside the measured window (entry points
``simulate``, ``serve``, ``run_sweep``), from the program's compile log.
Set-up warms every shape a cell uses, so this should read 0."""


def read(obs):
    return obs["counters"].get("window_compiles")
