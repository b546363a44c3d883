"""Device-idle time of the traced serve call per boundary, in ms: the host
work of the serving layer (table rebuild, ``device_get``, admission,
dispatch, retire) that the device waits through."""


def read(obs):
    tr, n = obs["trace"], obs["counters"].get("traced_boundaries", 0)
    if tr is None or not n:
        return None
    return 1e3 * tr.idle_s / n
