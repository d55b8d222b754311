"""Model FLOPs per token of training: forward and backward, no remat.

Each reference family counts its own forward, next to its loss:
``reference.<family>.forward_flops(cfg, seq)``, where ``<family>`` is the
configuration file's ``"reference"``. A family is added as a new module,
with no edit here. Training is three times the forward: the backward
computes the gradients of both operands of each product.
"""
import importlib


def forward_per_token(cfg, seq):
    name = "reference." + cfg["reference"]
    fam = importlib.import_module(name)
    if not hasattr(fam, "forward_flops"):
        raise AttributeError(f"{name} has no forward_flops(cfg, seq): each "
                             f"reference family counts its own forward")
    return fam.forward_flops(cfg, seq)


def train_per_token(cfg, seq):
    return 3 * forward_per_token(cfg, seq)
