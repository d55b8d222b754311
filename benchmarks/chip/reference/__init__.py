"""Plain float32 reference of the training step the benchmark drives.

It imports nothing of the program under test. ``common`` holds the
numerics shared by the model families, and ``train`` runs the sparsified
data-parallel training steps: the per-worker REGTOP-k of Algorithm 1 with
error feedback, the mean of the workers' sparse gradients, and Adam.

A family is one module (``xlstm``), named by a configuration file's
``"reference"`` and found by that name, so a new family is a new module
and nothing else. It provides:

- ``param_specs(cfg)``: ``[(path, shape, init)]`` of its parameters;
- ``nll(params, tokens, targets, cfg, mode)``: the summed next-token
  loss of one sequence;
- ``forward_flops(cfg, seq)``: model FLOPs per token of the forward, for
  ``flops.py``.
"""
