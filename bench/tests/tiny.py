"""A tiny copy of the benchmark for CPU rehearsals: the repository's
``BENCHMARK.json`` and ``bench/`` copied into a temporary root, with every
configuration and mix cut to a size a test holds (d stays 128).

The copy also holds the open-loop cell ``copydays-sift.online`` (the
``online`` mix through the wall-clock batcher), which ``BENCHMARK.json``
does not list yet: its harness is rehearsed here so that a later
benchmark change adds the cell by entries alone."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SEED = 2**33 + 5  # past 32 bits, as a run's seed may be
SECONDS = 1.5
SAMPLE_ROWS = 256

CONFIGS = {
    "copydays-sift": {"data": {"n_images": 120, "desc_per_image": 30},
                      "tree": {"fanouts": [8, 8], "sample": 2000}},
}
MIXES = {
    "batch": {"images_per_call": 4, "pool_calls": 2, "buckets": [128]},
    "online": {"rate": 20.0, "buckets": [64, 128], "wait_s": 10},
}


ONLINE = "copydays-sift.online"


def add_online_cell(bench):
    bench["workloads"].append({
        "name": ONLINE, "config": "copydays-sift", "traffic": "online",
        "chips": 1, "why": "open loop through the wall-clock batcher"})
    bench["end_to_end"].append({
        "name": "p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
        "source": "host_clock", "workloads": [ONLINE]})
    for name, unit, layer in (("queue_wait_ms.online", "ms", "batcher"),
                              ("batch_fill_pct.online", "%", "batcher"),
                              ("device_idle_pct.online", "%", "device")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "host_clock", "layer": layer, "moves": "p95_ms",
            "workloads": [ONLINE]})


def edit_json(path, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp) -> str:
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    edit_json(os.path.join(root, "BENCHMARK.json"), add_online_cell)
    for name, upd in CONFIGS.items():
        edit_json(os.path.join(root, "bench", "configs", name + ".json"),
                  lambda c: [c[k].update(v) for k, v in upd.items()])
    for name, upd in MIXES.items():
        edit_json(os.path.join(root, "bench", "traffic", name + ".json"),
                  lambda m: m.update(upd))
    return root


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]] + [ONLINE]


def measure(root, cell, trace=False):
    import run

    return run.measure(run.Spec(cell, root=root), SEED, SECONDS, trace,
                       require_tpu=False, sample_rows=SAMPLE_ROWS)
