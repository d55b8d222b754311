"""1 - (union of device op intervals / traced window), in %, on the
worst device."""


def read(red):
    return max(100.0 * (1 - d["busy_ns"] / d["window_ns"])
               for d in red["devices"].values())
