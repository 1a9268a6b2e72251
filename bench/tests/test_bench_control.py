"""The control: the plain reference computed in bfloat16, put in the
program's place, fails the comparison; and the harness refuses to run
without a TPU or without the program."""

import os
import shutil
import subprocess
import sys

import pytest

import control
import run
import system as system_lib
import tiny
from reference import Reference, verdict


@pytest.mark.parametrize("cell", tiny.cells())
def test_bf16_reference_is_not_correct(tmp_path, cell):
    root = tiny.make_root(tmp_path)
    spec = run.Spec(cell, root=root)
    cfg = spec.config
    corpus, levels = system_lib.make_data(cfg, tiny.SEED)
    queries = control.queries(spec, tiny.SEED, tiny.SAMPLE_ROWS, corpus)
    ref = Reference(corpus, levels, cfg["search"]["k"])
    ids, dists = ref.control_knn(queries)
    numbers = ref.compare(queries, ids, dists)
    numbers["unanswered"] = 0
    correct, checks = verdict(numbers, cfg["checks"])
    assert not correct, checks
    exact = ref.compare(queries, *ref.knn(queries)[1:])
    assert exact["wrong_answers"] == exact["unordered_answers"] == 0
    assert exact["missed_neighbours"] == 0


def _run_py(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", tiny.cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_run_py_exits_nonzero_without_a_tpu():
    p = _run_py(tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_py_exits_nonzero_with_the_benchmark_alone(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
