"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``, whose ``kind`` picks a
generator in ``loads.py``). The run makes the data, tree and index from the
seed through the program's normal path, warms the cell's own buckets
(set-up, reported as ``setup_s``), measures one window of ``--seconds`` on
the wall clock, then compares a seeded sample of what the window answered
with the plain reference (``reference.py``). With ``--trace 1`` the window
runs under the profiler and the line carries the per-layer metrics, each
read by ``metrics/<name>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: each compared number beside its limit, which are
also the last lines on standard error. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SAMPLE_ROWS = 8192  # answers compared per run (online: whole requests)
# A traced run measures at most this long: past about a million events the
# profiler drops device events (on a TPU v5e a 30 s trace of the dense scan
# lost 3.5 s of device activity).
TRACE_SECONDS = 10.0
NO_CHIP = 3


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Spec:
    """A cell as the data files describe it."""

    def __init__(self, workload: str, root: str = ROOT):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"known: {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.root = root
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.cell["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.mix = load_json(os.path.join(root, "bench", "traffic",
                                          self.cell["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        reported = {m["name"] for m in self.end_to_end}

        def applies(m):
            if "workloads" in m:
                return workload in m["workloads"]
            return m["moves"] in reported

        self.per_layer = [m for m in bench["per_layer"] if applies(m)]


class Run:
    """What the per-layer readers see."""

    def __init__(self, spec, window, reference, trace, peaks, spans):
        self.workload = spec.name
        self.config = spec.config
        self.window = window
        self.reference = reference
        self.trace = trace
        self.peaks = peaks
        self.spans = spans
        self.dispatch_queries = window.dispatch_queries
        self.notes = {}


def read_metric(root: str, name: str, run):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info(chips: int):
    """``(platform, kind, count)``, or exit when the chips are not there."""
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{platform} device(s)", file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return platform, kind, len(devices)


def memory_peak(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def measure(spec, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, sample_rows: int = SAMPLE_ROWS):
    """Set up, run the window, compare. Returns the result dict and the
    lines to print on standard error."""
    import jax

    import loads
    import system as system_lib
    import work
    from reference import Reference, verdict

    chips = spec.cell["chips"]
    if require_tpu:
        platform, kind, count = device_info(chips)
        peaks = work.peaks_for(kind)
    else:
        d = jax.devices()[0]
        platform, kind, count = d.platform, d.device_kind, len(jax.devices())
        peaks = None
    cache = enable_compile_cache() if require_tpu else None
    cfg, mix = spec.config, spec.mix
    kind_cls = loads.KINDS[mix["kind"]]
    system = system_lib.build(cfg, seed, buckets=mix.get("buckets"))
    load = kind_cls(mix, cfg, system, seed)
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    load.prepare(seconds)
    t0 = time.perf_counter()
    load.warm(system.session)
    system.timings["warm_s"] = time.perf_counter() - t0
    recompiles0 = system.session.recompiles()

    log_dir = os.path.join(spec.root, ".bench", "profile")
    spans = []
    reduced = None
    if trace:
        from repro import obs

        shutil.rmtree(log_dir, ignore_errors=True)
        tracer = obs.Tracer()
        obs.set_tracer(tracer)
        jax.profiler.start_trace(log_dir)
    # objects made in set-up are never garbage: keep the collector's full
    # passes over them out of the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    with jax.profiler.TraceAnnotation("bench.window"):
        window = load.run(system.session, seconds, sample_rows)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
        obs.set_tracer(None)
        spans = [(s.name, s.t1 - s.t0) for s in tracer.spans
                 if s.kind != "event"]
    recompiles = system.session.recompiles() - recompiles0
    peak = memory_peak(chips)
    session_notes = {
        "q_cap_overflow": system.session.metrics.q_cap_overflow,
        "recompiles_in_window": recompiles,
        "layouts": sorted({p["layout"] for p in system.session.plan_summary()}),
        "buckets": list(system.session.buckets),
    }
    corpus, levels = system.corpus, system.levels
    timings = system.timings
    del system, load
    gc.collect()

    t0 = time.perf_counter()
    reference = Reference(corpus, levels, cfg["search"]["k"])
    timings["reference_descent_s"] = time.perf_counter() - t0
    numbers = reference.compare(window.queries, window.ids, window.dists)
    numbers["unanswered"] = window.failed
    if len(window.queries):
        numbers["mean_leaf_rows"] = float(
            reference.leaf_sizes[reference.leaves(window.queries)].mean())
    correct, checks = verdict(numbers, cfg["checks"])
    t_ref = time.perf_counter() - t0
    if trace:
        import profile_reduce

        t0 = time.perf_counter()
        reduced = profile_reduce.reduce_dir(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        timings["trace_read_s"] = time.perf_counter() - t0

    run = Run(spec, window, reference, reduced, peaks, spans)
    if trace:
        metrics = {}
        for m in spec.per_layer:
            v = read_metric(spec.root, m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = dict(window.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in spec.end_to_end}
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(window.attempted),
              "failed": int(window.failed), "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = checks
    info = dict(window.notes, **session_notes, **run.notes,
                setup_s=setup_s, reference_s=t_ref, compile_cache=cache,
                compared_rows=len(window.queries),
                **{k: round(v, 3) for k, v in timings.items()})
    info.update({f"{k} (not compared)": v for k, v in numbers.items()
                 if k not in checks})
    lines = [f"{k}: {v}" for k, v in info.items()]
    lines += [f"check {n}: {c['value']} (limit {c['limit']})"
              for n, c in checks.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = Spec(args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"the program is not in {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [src, HERE]
    # the TPU runtime logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    result, lines = measure(spec, args.seed, args.seconds, bool(args.trace))
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
