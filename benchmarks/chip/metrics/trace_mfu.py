"""The whole step's share of the chips' bf16 peak over the traced steps,
in %: tokens of the traced steps × model FLOPs per token (``flops.py``)
over (traced window × chips × peak). It bounds every kernel's roofline
share: a kernel taken off the path leaves its own share silent, and this
one still counts the step."""


def read(red):
    need = ("tokens_per_step", "flops_per_token", "chips")
    if not all(k in red for k in need) or not red["window_s"]:
        return None
    work = red["steps"] * red["tokens_per_step"] * red["flops_per_token"]
    return 100.0 * work / (red["window_s"] * red["chips"]
                           * red["peaks"]["bf16_flops_per_s"])
