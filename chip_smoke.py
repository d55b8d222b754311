"""Smoke run of the REGTOP-k sparsified train step on TPU chips.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the sparse all-gather

One chip: full-width xlstm-125m (12 layers, d_model 768, vocab 50,304;
random weights from the seed) takes STEPS fused REGTOP-k ``--comm sparse``
steps on one fixed batch, built through ``repro.launch.train``'s own
``parse_args`` / ``build_run`` and ``repro.train.step``. The same steps then
run with ``--pipeline reference``, the dense jnp oracle. The losses must be
finite and fall, and the two traces must agree within LOSS_TOL.

Four chips (``--chips 4``, and nothing else): data parallelism over four
chips, ``--comm sparse`` (the sparse all-gather) against ``--comm simulate``
(the dense all-reduce of the same sparsified gradient), same model and
batch, xlstm-125m at full width cut to FOUR_CHIP_LAYERS layers. The traces
must be equal (FOUR_CHIP_TOL), and every device must hold memory.

Times printed here are smoke timings of a few steps, not a benchmark. The
last line of stdout is one JSON object naming the device. Without a TPU the
script exits non-zero before it builds anything.
"""
import argparse
import json
import math
import os
import sys
import time

ARCH = "xlstm-125m"
# xlstm-125m's sequential mLSTM scan keeps its (B, H, 384, 384) cell per
# step for the backward pass, so the carry grows with the sequence: 4,096
# tokens need 27.7 GB on one v5e (compiled for a described chip); 1,024 is
# the longest power of two that fits beside the training state.
SEQ = 1024
BATCH = 1                     # per chip
STEPS = 4
SEED = 0
# the four-chip step compiles for about 4 minutes at 12 layers and 1 at 2
# (described v5e:2x2 compiles); one mLSTM and one sLSTM block keep both
# block kinds and the full-width embedding in the synced gradient
FOUR_CHIP_LAYERS = 2
# Fused and reference run the same forward and backward and, for one
# worker, select the same support, so their loss traces agree; 1e-4 is
# the 4 decimals they hold on the CPU, far below the 0.28 to 0.51 the
# loss falls per step.
LOSS_TOL = 1e-4
# --comm sparse and --comm simulate combine the same sparsified
# gradients, and on four v5e chips their traces agree bit for bit; a lost
# or misplaced pair of the sparse all-gather would show.
FOUR_CHIP_TOL = 0.0


def launcher_args(*extra):
    from repro.launch.train import parse_args
    return parse_args(["--arch", ARCH, "--sparsifier", "regtopk",
                       "--sparsity", "0.001", "--seed", str(SEED),
                       "--fixed-batch", *extra])


def loss_trace(run, mesh, batch_per_chip, seq, steps, label):
    """Train ``steps`` steps of ``run`` on one fixed batch. Returns the
    loss trace; prints compile and per-step seconds."""
    import jax
    from repro.data import lm_batch
    from repro.train.step import (build_parallel, build_train_step,
                                  init_train_state, resolve_model_cfg)
    pal = build_parallel(mesh)
    key = jax.random.PRNGKey(SEED)
    n_dp = mesh.shape["data"]
    batch = lm_batch(resolve_model_cfg(run), batch_per_chip * n_dp, seq,
                     SEED, 0)
    with jax.set_mesh(mesh):
        params, opt_state, ef_state = init_train_state(run, mesh, pal, key)
        step, _, _ = build_train_step(run, mesh, pal)
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            params, opt_state, ef_state, batch, key).compile()
        compile_s = time.perf_counter() - t0
        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            params, opt_state, ef_state, m = compiled(
                params, opt_state, ef_state, batch, key)
            losses.append(float(m["loss"]))          # waits for the step
            step_s.append(time.perf_counter() - t0)
    print(f"[smoke] {label}: compile {compile_s:.2f} s; per-step seconds "
          f"(smoke timing, not a benchmark) {step_s}")
    print(f"[smoke] {label}: losses {losses}")
    return losses


def peak_bytes(dev):
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def check(cond, msg):
    if not cond:
        raise SystemExit(f"[smoke] FAILED: {msg}")


def check_traces(a, b, label_a, label_b, tol):
    check(all(math.isfinite(x) for x in a + b),
          f"non-finite loss: {label_a} {a}, {label_b} {b}")
    diff = max(abs(x - y) for x, y in zip(a, b))
    print(f"[smoke] max |{label_a} - {label_b}| loss = {diff:.3e} "
          f"(tolerance {tol:.0e})")
    check(diff <= tol, f"{label_a} and {label_b} loss traces differ "
          f"by {diff:.3e} > {tol:.0e}")


def one_chip(make_mesh, compress_strategy):
    import jax
    fused = launcher_args("--comm", "sparse", "--pipeline", "fused")
    ref = launcher_args("--comm", "sparse", "--pipeline", "reference")
    from repro.launch.train import build_run
    run_f, run_r = build_run(fused), build_run(ref)
    cfg = run_f.model
    print(f"[smoke] config {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}; seq {SEQ}, batch {BATCH};"
          f" regtopk@{run_f.sparsifier.sparsity} comm=sparse, adam")
    print(f"[smoke] compress strategy: {compress_strategy(run_f.sparsifier)}"
          f" (fused), {compress_strategy(run_r.sparsifier)} (reference)")
    mesh = make_mesh(1, 1)
    lf = loss_trace(run_f, mesh, BATCH, SEQ, STEPS, "fused")
    print(f"[smoke] peak_bytes_in_use after fused: "
          f"{peak_bytes(jax.devices()[0])}")
    lr = loss_trace(run_r, mesh, BATCH, SEQ, STEPS, "reference")
    print(f"[smoke] peak_bytes_in_use after reference: "
          f"{peak_bytes(jax.devices()[0])}")
    check(all(math.isfinite(x) for x in lf), f"non-finite fused loss {lf}")
    check(all(x < lf[0] for x in lf[1:]) and lf[-1] == min(lf),
          f"fused loss does not fall on the fixed batch: {lf}")
    check_traces(lf, lr, "fused", "reference", LOSS_TOL)


def four_chips(make_mesh, compress_strategy):
    import jax
    sparse = launcher_args("--comm", "sparse", "--pipeline", "fused",
                           "--data", "4")
    dense = launcher_args("--comm", "simulate", "--pipeline", "fused",
                          "--data", "4")
    import dataclasses
    from repro.launch.train import build_run
    run_s, run_d = (dataclasses.replace(r, model=dataclasses.replace(
        r.model, n_layers=FOUR_CHIP_LAYERS))
        for r in (build_run(sparse), build_run(dense)))
    cfg = run_s.model
    print(f"[smoke] config {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}; seq {SEQ}, batch "
          f"{BATCH} per chip x 4 chips (data parallel); "
          f"regtopk@{run_s.sparsifier.sparsity}, adam; compress strategy: "
          f"{compress_strategy(run_s.sparsifier)}")
    mesh = make_mesh(4, 1)
    ls = loss_trace(run_s, mesh, BATCH, SEQ, STEPS, "comm=sparse")
    ld = loss_trace(run_d, mesh, BATCH, SEQ, STEPS, "comm=simulate")
    peaks = [peak_bytes(d) for d in jax.devices()[:4]]
    print(f"[smoke] peak_bytes_in_use per device: {peaks}")
    check(all(x < ls[0] for x in ls[1:]),
          f"sparse loss does not fall on the fixed batch: {ls}")
    check_traces(ls, ld, "comm=sparse", "comm=simulate", FOUR_CHIP_TOL)
    # every rank holds its replica of the params plus its EF and ZeRO-1
    # shard; a device near zero means the work went to another one
    check(min(peaks) > 0.5 * max(peaks),
          f"memory is not spread over the four devices: {peaks}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the four-chip sparse-vs-dense phase")
    chips = ap.parse_args().chips

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"[smoke] needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.launch.train import compress_strategy
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    print(f"[smoke] jax {jax.__version__}, {len(devices)} x "
          f"{devices[0].device_kind}")
    (four_chips if chips == 4 else one_chip)(make_mesh, compress_strategy)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
