"""Every configuration, traffic, limit and metric file the benchmark
names loads, and each configuration matches the arch it names; a new cell
is found by its files alone, and so is a new model family."""
import importlib

import pytest

import cells
import checks
import program
import tiny

BENCH = cells.load_benchmark()
NAMES = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", NAMES)
def test_cell_files_load(name):
    cell = cells.load_cell(name)
    assert cell["traffic"]["chips"] == cell["chips"]
    assert cell["limits"] is not None, f"limits/{name}.json is missing"
    for key in checks.NAMES:
        assert cell["limits"][key] > 0
    conf = cell["config"]
    assert cell["traffic"]["seq"] <= conf.get("max_train_seq_len",
                                              float("inf"))
    names = {m["name"] for m in cell["end_to_end"]}
    assert {"setup_s", "tokens_per_s"} <= names


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_matches_registered_arch(conf):
    """The file's numbers are the registered arch's, except the keys in
    ``reduced``, which the file records the source's value of; the
    parameter count is the program's and the reference's."""
    cell = next(cells.load_cell(w["name"]) for w in BENCH["workloads"]
                if w["config"] == conf["name"])
    file = cell["config"]
    cfg = program.model_config(file, conf["reduced"])
    assert set(conf["reduced"]) == set(file["reduced_from"])
    for key in conf["reduced"]:
        assert file["reduced_from"][key] != file[key]
    fam = importlib.import_module("reference." + file["reference"])
    ref_count = sum(int(__import__("numpy").prod(shape))
                    for _, shape, _ in fam.param_specs(file))
    assert cfg.param_count() == file["parameters"] == ref_count


def test_metric_readers():
    """Every per-layer metric has a reader; with nothing in the trace for
    it a reader returns None or a number, never fails."""
    empty = {"steps": 1, "j_local": 10, "window_s": 1e-8,
             "peaks": {"hbm_bytes_per_s": 1e9},
             "devices": {0: {"layer_ns": {}, "busy_ns": 5, "window_ns": 10,
                             "sync_exposed_ns": 0}}}
    for m in BENCH["per_layer"]:
        reader = importlib.import_module("metrics." + m["name"])
        value = reader.read(empty)
        assert value is None or value >= 0
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_new_cell_is_found_by_its_files(tmp_path):
    """A cell added as data: a throwaway traffic file, its limits and a
    BENCHMARK.json entry, found by name with no code changed."""
    bench, here, name = tiny.make(str(tmp_path), traffic_name="throwaway")
    cell = cells.load_cell(name, bench, here)
    assert cell["traffic"]["seq"] == 32
    assert cell["limits"] == tiny.LIMITS
    assert cell["config"]["d_model"] == 64


def test_layer_scopes_exist():
    """Every scope of layers.json names a callable of the program, and a
    missing one is an error, not a silent shift of ops between layers."""
    import trace_reduce
    with program.layer_scopes(trace_reduce.load_layers()):
        pass
    table = {"scope_prefix": "BENCH_",
             "scopes": [["repro.train.step", "no_such_call", "model"]]}
    with pytest.raises(AttributeError):
        with program.layer_scopes(table):
            pass


def test_cache_dir_is_set_after_jax_import():
    """The run imports JAX before it sets up the cache; the cache
    directory still reaches JAX's config, with or without
    JAX_COMPILATION_CACHE_DIR."""
    import os
    import subprocess
    import sys

    import conftest
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "import jax, harness\n"
            "path = harness.enable_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == path, path\n"
            "print(path)\n"
            % (conftest.BENCH, os.path.join(conftest.ROOT, "src")))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    for extra, want in ({}, os.path.join(conftest.ROOT, ".jax_cache")), (
            {"JAX_COMPILATION_CACHE_DIR": "/nonexistent/given"},
            "/nonexistent/given"):
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120,
                           env=dict(env, JAX_PLATFORMS="cpu", **extra))
        assert p.returncode == 0, p.stderr
        assert p.stdout.strip() == want


FAMILY = '''"""A toy family: an embedding and an output head."""
from reference.common import einsum, nll_sum


def param_specs(cfg):
    d, v = cfg["d_model"], cfg["vocab_size"]
    return [(("embed", "head"), (d, v), d ** -0.5),
            (("embed", "tok"), (v, d), 0.02)]


def nll(params, tokens, targets, cfg, mode):
    x = params["embed"]["tok"][tokens]
    return nll_sum(x, params["embed"]["head"], targets, mode)


def forward_flops(cfg, seq):
    return 2 * cfg["d_model"] * cfg["vocab_size"]
'''
STAGE_METRIC = '''from metrics import stage_ms


def read(red):
    return stage_ms(red, "toy_router")
'''
COUNTER_METRIC = '''from metrics import counter


def read(red):
    value = counter(red, "toy_dropped")
    return None if value is None else 100.0 * value
'''


def test_new_family_is_found_by_its_files(tmp_path, monkeypatch,
                                          request):
    """A configuration of a family that exists only under tmp_path, with a
    stage and a counter that only its metric files name: loaded, counted
    and read through the harness's own lookups, with no file of the
    benchmark edited."""
    import sys

    import jax
    import numpy as np

    import flops
    import harness
    import metrics
    import reference
    import run
    from reference.train import init_params

    fam_dir, metric_dir = tmp_path / "reference", tmp_path / "metrics"
    fam_dir.mkdir()
    metric_dir.mkdir()
    (fam_dir / "toyfam.py").write_text(FAMILY)
    (metric_dir / "toy_router_ms.py").write_text(STAGE_METRIC)
    (metric_dir / "toy_dropped_share.py").write_text(COUNTER_METRIC)
    monkeypatch.setattr(reference, "__path__",
                        list(reference.__path__) + [str(fam_dir)])
    monkeypatch.setattr(metrics, "__path__",
                        list(metrics.__path__) + [str(metric_dir)])
    request.addfinalizer(lambda: [sys.modules.pop(m, None) for m in (
        "reference.toyfam", "metrics.toy_router_ms",
        "metrics.toy_dropped_share")])

    config = {"arch": "toy", "reference": "toyfam", "source": "test",
              "d_model": 16, "vocab_size": 64}
    bench, here, name = tiny.make(str(tmp_path / "bench"), config=config)
    bench["per_layer"] = [
        {"name": "toy_router_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "toy", "moves": "tokens_per_s"},
        {"name": "toy_dropped_share", "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "toy",
         "moves": "tokens_per_s"}]
    cell = cells.load_cell(name, bench, here)

    fam = harness.family(cell)
    assert fam.__file__ == str(fam_dir / "toyfam.py")
    params = init_params(fam, cell["config"], jax.random.PRNGKey(0))
    tokens = np.arange(8, dtype=np.int32)
    assert np.isfinite(float(fam.nll(params, tokens, tokens,
                                     cell["config"], "f32")))
    assert flops.train_per_token(cell["config"], 32) == 3 * 2 * 16 * 64

    red = {"steps": 2, "devices": {0: {
        "layer_ns": {}, "stage_ns": {"toy_router": 4e6, "fwd": 1e6}}},
        "counters": {"toy_dropped": 0.125, "loss": 4.0}}
    assert run.per_layer(cell, red) == {
        "toy_router_ms": {"value": 2.0, "unit": "ms"},
        "toy_dropped_share": {"value": 12.5, "unit": "%"}}
    # a reduction with neither stages nor counters reports neither
    assert run.per_layer(cell, {"steps": 2, "devices": {
        0: {"layer_ns": {}}}}) == {}
