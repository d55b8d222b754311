"""End-to-end behaviour tests: launcher CLI, example drivers, dry-run on a
tiny mesh — all via subprocess (device-count isolation)."""
import json
import os
import re
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def run_cmd(args, env_extra=None, timeout=1500):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + ROOT + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.update(env_extra or {})
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, (
        f"STDOUT:\n{out.stdout[-4000:]}\nSTDERR:\n{out.stderr[-4000:]}")
    return out.stdout


def test_train_launcher_smoke(tmp_path):
    # --fixed-batch: the synthetic stream is uniform-random tokens, so loss
    # only decreases measurably when overfitting one batch
    out = run_cmd(["-m", "repro.launch.train", "--arch", "granite-8b",
                   "--smoke", "--steps", "8", "--data", "2", "--model", "2",
                   "--devices", "4", "--sparsifier", "regtopk",
                   "--comm", "sparse", "--log-every", "4", "--fixed-batch",
                   "--checkpoint-dir", str(tmp_path / "ck")])
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out)]
    assert losses and losses[-1] < losses[0]
    assert any(f.endswith(".params.npz") for f in os.listdir(tmp_path / "ck"))


def test_train_launcher_allocation_smoke(tmp_path):
    """Convergence smoke for density allocation (DESIGN.md §2.6): the
    fused pipeline with per-layer adaptive budgets must still overfit
    the fixed batch, and the launcher must thread --allocation through."""
    out = run_cmd(["-m", "repro.launch.train", "--arch", "stablelm-3b",
                   "--smoke", "--steps", "8", "--data", "2", "--model", "1",
                   "--devices", "2", "--sparsifier", "regtopk",
                   "--comm", "sparse", "--pipeline", "fused",
                   "--allocation", "adaptive", "--num-segments", "6",
                   "--log-every", "4", "--fixed-batch"])
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out)]
    assert losses and losses[-1] < losses[0]
    assert "compress strategy: xla" in out


def test_chip_smoke_refuses_without_tpu():
    """chip_smoke.py exits non-zero with no result line when JAX finds no
    TPU, and stops before it builds the model."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "[smoke] config" not in out.stdout
    assert re.search(r"needs 1 TPU chip\(s\); JAX found \d+ cpu device",
                     out.stderr), out.stderr


def test_dryrun_tiny_mesh(tmp_path):
    out_json = str(tmp_path / "dr.json")
    out = run_cmd(["-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
                   "--shape", "decode_32k,long_500k", "--mesh", "2x2",
                   "--out", out_json])
    assert "0 failed" in out
    data = json.load(open(out_json))
    assert len(data["results"]) == 2
    for r in data["results"]:
        assert r["hlo_flops"] > 0
        assert r["memory"]["argument_size_in_bytes"] > 0


def test_dryrun_multipod_tiny():
    out = run_cmd(["-m", "repro.launch.dryrun", "--arch",
                   "granite-moe-3b-a800m", "--shape", "train_4k",
                   "--mesh", "2x2x2"])
    assert "0 failed" in out


def test_example_quickstart():
    out = run_cmd(["examples/quickstart.py"])
    assert "greedy decode" in out


def test_example_train_100m_tiny():
    out = run_cmd(["examples/train_100m.py", "--steps", "6", "--tiny",
                   "--batch", "4", "--seq", "64"])
    assert "loss" in out


def test_example_serve_batched():
    out = run_cmd(["examples/serve_batched.py"])
    assert "sliding" in out
