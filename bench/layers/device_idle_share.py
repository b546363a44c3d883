"""Share of the traced window in which no operation ran on the device,
mean over the cell's devices, in percent."""


def read(obs):
    tr = obs["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_s / tr.window_s
