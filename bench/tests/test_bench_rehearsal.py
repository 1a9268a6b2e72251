"""Each cell's set-up, window and comparison, in process on the CPU at a
tiny size, and a cell added by files and entries alone."""

import json
import os

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", tiny.cells())
def test_cell_runs_and_is_correct(root, cell):
    result, lines = tiny.measure(root, cell)
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] > 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        e2e = [m["name"] for m in json.load(f)["end_to_end"]
               if cell in m.get("workloads", [cell])]
    assert sorted(result["metrics"]) == sorted(e2e)
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert lines[-1].startswith("check ")


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "copydays-sift.json")) as f:
        cfg = json.load(f)
    cfg["name"], cfg["search"]["k"] = "throwaway-sift", 5
    with open(os.path.join(bench, "configs", "throwaway-sift.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "throwaway.json"), "w") as f:
        json.dump({"kind": "closed_images", "images_per_call": 2,
                   "popularity": "zipf", "zipf_s": 1.1, "noise": 2.0,
                   "pool_calls": 3, "buckets": [64]}, f)
    with open(os.path.join(bench, "metrics", "calls.throwaway.py"),
              "w") as f:
        f.write("def read(run):\n    return run.window.notes['calls']\n")
    cell = "throwaway-sift.zipf"

    def add(b):
        b["configs"].append({"name": "throwaway-sift", "source": "x",
                             "file": "bench/configs/throwaway-sift.json",
                             "reduced": [], "why": "x"})
        b["workloads"].append({"name": cell, "config": "throwaway-sift",
                               "traffic": "throwaway", "chips": 1,
                               "why": "x"})
        for m in b["end_to_end"]:
            if m["name"] == "ms_per_image":
                m["workloads"].append(cell)
        b["per_layer"].append({"name": "calls.throwaway", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "ms_per_image",
                               "workloads": [cell]})

    tiny.edit_json(os.path.join(root, "BENCHMARK.json"), add)
    result, lines = tiny.measure(root, cell)
    assert result["correct"], lines
    assert sorted(result["metrics"]) == ["ms_per_image", "setup_s"]
    result, lines = tiny.measure(root, cell, trace=True)
    assert result["correct"], lines
    # no TPU plane on the CPU: the trace readers read nothing
    assert list(result["metrics"]) == ["calls.throwaway"]
    assert result["metrics"]["calls.throwaway"]["value"] >= 1
