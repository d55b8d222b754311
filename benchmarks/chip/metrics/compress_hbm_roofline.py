"""The gradient sync's share of its HBM roofline, in %.

12 bytes per entry of the worker's flat gradient (J_local) is the least
any REGTOP-k with error feedback moves: read g and the error (float32
each) and write the new error. That floor over the chip's HBM bandwidth,
divided by ``compress_ms``. The work has almost no FLOPs, so bandwidth
bounds it."""
from metrics.compress_ms import read as compress_ms


def read(red):
    ms = compress_ms(red)
    if not ms:
        return None
    floor_s = 12 * red["j_local"] / red["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
