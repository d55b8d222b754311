"""Plain float32 reference of the training step the benchmark drives.

It imports nothing of the program under test. ``common`` holds the
numerics shared by the model families, one module per family
(``xlstm``) holds the forward loss of one sequence, and
``train`` runs the sparsified data-parallel training steps: the
per-worker REGTOP-k of Algorithm 1 with error feedback, the mean of the
workers' sparse gradients, and Adam.
"""
