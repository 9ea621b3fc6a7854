"""The harness finds every piece of a cell by file name, refuses what it
cannot run, and never falls back to the CPU."""
import json
import shutil

import pytest

from bench import run
from bench.cell import (ROOT, UnknownWorkload, find_cell, load_peaks,
                        loop_class, metric_reader)

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_from_its_files(name):
    cell = find_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    assert callable(loop_class(cell))
    assert cell.limits
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_per_layer_metric_has_a_reader(name):
    assert callable(metric_reader(name))


def test_config_files_match_benchmark_json():
    for c in BENCH["configs"]:
        doc = json.loads((ROOT / c["file"]).read_text())
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])


def _new_cell_files(tmp_path, loop: str) -> dict:
    """A config, a mix of the given loop, limits and a per-layer metric
    added as files beside a copy of ``bench/``, with BENCHMARK.json
    entries; no file of the copy edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/tiny_f32.json").write_text(json.dumps(
        {"name": "tiny_f32", "source": "x", "reduced": [], "n": 64,
         "tile": 32, "dtype": "float32", "precision": "highest",
         "runtime": {"n_devices": 1, "backend": "jax"}}))
    (tmp_path / "bench/traffic/square.json").write_text(json.dumps(
        {"loop": loop, "hold": "handles",
         "operands": {"A": {"shape": ["n", "n"], "fill": "normal"}},
         "calls": [{"routine": "syrk", "args": ["A"], "out": "C"}],
         "compare": ["C"]}))
    (tmp_path / "bench/limits/tiny.syrk.json").write_text(
        json.dumps({"limits": {"err_C": 1e-6}}))
    (tmp_path / "bench/metrics/calls.tiny.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench["workloads"].append({"name": "tiny.syrk", "config": "tiny_f32",
                               "traffic": "square", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "calls.tiny", "unit": "calls",
                               "better": "lower", "source": "host_clock",
                               "layer": "runtime", "moves": "tflops",
                               "workloads": ["tiny.syrk"]})
    bench["end_to_end"][0]["workloads"].append("tiny.syrk")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def test_a_new_cell_needs_only_new_files(tmp_path):
    _new_cell_files(tmp_path, "closed")
    cell = find_cell("tiny.syrk", root=tmp_path)
    assert cell.config["n"] == 64 and cell.traffic["calls"][0]["routine"] == "syrk"
    assert [m["name"] for m in cell.per_layer] == ["calls.tiny"]
    assert {m["name"] for m in cell.end_to_end} == {"tflops", "setup_s"}
    assert metric_reader("calls.tiny", root=tmp_path)(None) == 42.0
    assert loop_class(cell, root=tmp_path).__module__ == "bench_loop_closed"


def test_a_new_kind_of_loop_is_a_new_file(tmp_path):
    """A mix that names a loop the benchmark does not have yet is run by
    the file of that name under ``bench/loops``, found like the rest."""
    _new_cell_files(tmp_path, "paced")
    (tmp_path / "bench/loops/paced.py").write_text(
        "class Loop:\n    def __init__(self, cell, seed, spans=None):\n"
        "        self.seed = seed\n")
    cell = find_cell("tiny.syrk", root=tmp_path)
    assert loop_class(cell, root=tmp_path)(cell, 7).seed == 7
    with pytest.raises(FileNotFoundError):
        loop_class(cell)            # the checkout has no such loop


@pytest.mark.parametrize("where,doc", [
    ("mix", {"arrivals": "poisson"}),
    ("call", {"calls": [{"routine": "gemm", "args": ["A", "B"], "out": "C",
                         "alpha": 2.0}]}),
    ("operand", {"operands": {"A": {"shape": ["n", "n"], "fill": "normal",
                                    "scale": 2}}}),
    ("config", {"seed_offset": "x"}),
])
def test_a_key_that_nothing_reads_is_refused(small_cell, where, doc):
    import dataclasses

    cell = small_cell("n8192.gemm")
    if where == "config":
        cell = dataclasses.replace(cell, config=dict(cell.config, **doc))
    else:
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, **doc))
    with pytest.raises(ValueError, match="unknown keys"):
        loop_class(cell)(cell, 1)


def test_a_precision_the_library_does_not_run_is_refused(small_cell):
    import dataclasses

    cell = small_cell("n8192.gemm")
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 precision="default"))
    with pytest.raises(ValueError, match="precision"):
        loop_class(cell)(cell, 1)


def test_unknown_workload_is_refused():
    with pytest.raises(UnknownWorkload):
        find_cell("no.such.cell")
    assert run.main(["--workload", "no.such.cell", "--seed", "1",
                     "--seconds", "1"]) != 0


def test_main_refuses_a_device_that_is_not_a_tpu(capsys):
    """In the test's own process JAX sees the CPU: no result, non-zero."""
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == "" and "no TPU" in err


def test_peaks_of_a_kind_not_in_the_table_are_an_error():
    assert load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        load_peaks("TPU v99")


def test_seed_beyond_32_bits_gives_the_same_inputs_each_time():
    from bench.generator import make, rng

    spec = {"shape": ["n", 3], "fill": "normal"}
    big = 2 ** 31 + 12345
    a = make(spec, {"n": 4}, rng(big, 0), "float32")
    b = make(spec, {"n": 4}, rng(big, 0), "float32")
    c = make(spec, {"n": 4}, rng(big + 1, 0), "float32")
    assert (a == b).all() and not (a == c).all()


def test_size_expressions():
    from bench.generator import size

    assert size("n - tile", {"n": 8192, "tile": 1024}) == 7168
    assert size(5, {}) == 5
    with pytest.raises(ValueError):
        size("__import__('os')", {})
