"""Device ms per step of the ZeRO-1 Adam update (repro/optim/,
repro/train/step.py), the master all-gather included."""


def read(red):
    vals = [d["layer_ns"].get("optimizer", 0)
            + d["layer_ns"].get("optimizer_collective", 0)
            for d in red["devices"].values()]
    if not any(vals):
        return None
    return sum(vals) / len(vals) / red["steps"] / 1e6
