"""Device time per program phase, idle gaps named by the program's own
spans, and what the program's tracer costs, for one benchmark cell.

    python bench/phases.py --workload <cell> --seed <n> --seconds <s> \
        [--pairs <n>] [--out <dir>]

``run.py --trace 1`` names device time by XLA op (``%sort.31``), numbers
that change with every recompile, and names idle gaps by the harness's
``bench.*`` annotations only. This tool sets the cell up as ``run.py``
does, then

1. measures ``ms_per_image`` in ``--pairs`` pairs of ``--seconds``
   windows, the first of each pair with no tracer and the second with a
   ``repro.obs.Tracer`` installed (no profiler): what the program's spans
   cost when on; and the slowest call of each window, split into its
   spans where the tracer was on;
2. records one profiled window (at most ``run.TRACE_SECONDS``) with the
   tracer installed and reduces it: device self time per phase, the
   innermost ``repro.*`` ``jax.named_scope`` of each operation
   (:mod:`repro.core.phases`), read from the optimized HLO of the
   programs that ran (``SearchSession.compiled_hlo``); the operations
   that took most time with their phase; and idle gaps named by the
   innermost ``bench.*`` annotation or program span covering them.

The last line of standard output is one JSON object. With ``--out`` the
programs' HLO text and that line are also written there.
"""

from __future__ import annotations

import argparse
import bisect
import gc
import json
import os
import re
import shutil
import sys
import time

import profile_reduce as pr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MODULES_LINE = "XLA Modules"
SCOPE_PREFIX = "repro."
UNSCOPED = "unscoped"  # no repro.* scope, or a module not compiled here
UNATTRIBUTED = "unattributed"  # an op its module's HLO text does not name

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(op_name: str) -> str | None:
    """The innermost ``repro.*`` component of an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def op_scopes(hlo_texts) -> dict:
    """``{module: [{instruction: phase or None}]}`` from optimized HLO
    texts (``compiled.as_text()``), one table per text: programs of one
    module name (the rungs of a bucket ladder) each keep their own."""
    out = {}
    for text in hlo_texts:
        m = _MODULE.match(text)
        if not m:
            continue
        table = {}
        for line in text.splitlines():
            ins = _INSTRUCTION.match(line)
            if ins:
                name = _OP_NAME.search(line)
                table[ins.group(1)] = scope_of(name.group(1)) if name else None
        out.setdefault(m.group(1), []).append(table)
    return out


def phase_of(scopes: dict, module: str | None, op: str) -> str:
    """The phase of instruction ``op`` run by ``module``: its scope, where
    every compiled program of that module name that has ``op`` agrees."""
    tables = scopes.get(module) if module is not None else None
    if not tables:
        return UNSCOPED
    found = {t[op] for t in tables if op in t}
    if len(found) != 1:
        return UNATTRIBUTED
    return found.pop() or UNSCOPED


def self_times(ops):
    """``[[name, start, end, self]]`` of ``[(name, start, end)]``: each
    operation's time less that of the operations nested in it (an XLA
    ``while`` holds the ops of its body)."""
    out, stack = [], []  # stack: indices into out of the enclosing ops
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(b, parent[2]) - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return out


def _module_at(modules, t) -> str | None:
    """The module (``<name>(<id>)`` events as sorted ``(start, end,
    name)``) whose run covers ``t``, without its id."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][1] >= t:
        return modules[i][2].split("(")[0]
    return None


def _sorted_desc(totals: dict) -> list:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])]


def reduce_phases(planes, scopes: dict, span_names=()) -> dict | None:
    """Phases and named gaps of the ``bench.window`` of ``planes`` (the
    form :func:`profile_reduce.load_planes` gives). ``scopes``:
    :func:`op_scopes` of the programs run in the window. ``span_names``:
    the program's span names, which name host time beside the ``bench.*``
    annotations. Times are seconds, mean over chips; ``None`` without a
    window or a device operation in it."""
    names = frozenset(span_names)
    host, devices = [], []
    for pname, lines in planes:
        if pname.startswith(pr.DEVICE_PREFIX):
            devices.append((
                [ev for lname, evs in lines if lname == pr.OPS_LINE
                 for ev in evs],
                sorted((s, s + d, n) for lname, evs in lines
                       if lname == MODULES_LINE for n, s, d in evs),
            ))
        elif pname == "/host:CPU":
            host.extend(ev for _, evs in lines for ev in evs
                        if ev[0].startswith("bench.") or ev[0] in names)
    windows = [(s, s + d) for n, s, d in host if n == pr.WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    annotations = sorted(((s, s + d, n) for n, s, d in host
                          if n != pr.WINDOW), key=lambda a: (a[0], -a[1]))
    phases, op_time, op_phase, idle = {}, {}, {}, {}
    gaps, busy = [], []
    for ops, modules in devices:
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((name.split(" = ")[0], a, b))
        if not clipped:
            continue
        for op, a, b, own in self_times(clipped):
            phase = phase_of(scopes, _module_at(modules, a), op.lstrip("%"))
            op_phase.setdefault(op, phase)
            op_time[op] = op_time.get(op, 0.0) + (b - a) * 1e-9
            phases[phase] = phases.get(phase, 0.0) + own * 1e-9
        merged = pr._union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                doing = pr._doing(annotations, (a + b) / 2)
                gaps.append([doing, (b - a) * 1e-9])
                idle[doing] = idle.get(doing, 0.0) + (b - a) * 1e-9
    if not busy:
        return None
    n = len(busy)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:pr.TOP]
    return {
        "busy_s": sum(busy) / n,
        "window_s": (w1 - w0) * 1e-9,
        "device_phases": [[p, t / n] for p, t in _sorted_desc(phases)],
        "device_ops": [[op, t / n, op_phase[op]] for op, t in top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:pr.TOP],
        "idle_by_annotation": [[k, t / n] for k, t in _sorted_desc(idle)],
    }


def scoped_share_pct(reduced: dict) -> float:
    """Share of device-busy time that ``repro.*`` scopes hold."""
    scoped = sum(t for p, t in reduced["device_phases"]
                 if p.startswith(SCOPE_PREFIX))
    return 100.0 * scoped / reduced["busy_s"]


def ms_per_call(spans) -> dict:
    """Mean milliseconds per ``engine.execute`` span of each of its
    children, by name."""
    calls = {s.span_id for s in spans if s.name == "engine.execute"}
    out = {}
    for s in spans:
        if s.parent_id in calls:
            out[s.name] = out.get(s.name, 0.0) + 1e3 * (s.t1 - s.t0)
    return {name: t / len(calls) for name, t in out.items()}


def slowest_call(spans) -> dict:
    """The longest ``engine.execute`` span, in milliseconds, with its
    children's: where a slow call spent its time."""
    call = max((s for s in spans if s.name == "engine.execute"),
               key=lambda s: s.t1 - s.t0)
    out = {"call_ms": 1e3 * (call.t1 - call.t0)}
    for s in spans:
        if s.parent_id == call.span_id:
            out[s.name] = 1e3 * (s.t1 - s.t0)
    return out


def host_ms_per_call(spans) -> float | None:
    """Mean host time per ``engine.execute`` span outside the device
    wait: its ``session.*`` children other than ``session.wait``."""
    host = [t for name, t in ms_per_call(spans).items()
            if name.startswith("session.") and name != "session.wait"]
    return sum(host) if host else None


def measure(spec, seed: int, seconds: float, pairs: int,
            require_tpu: bool = True, out: str | None = None) -> dict:
    """Set the cell up, run the tracer-cost windows and the profiled
    window; returns the result line."""
    import jax

    import loads
    import run
    import system as system_lib
    from repro import obs

    chips = spec.cell["chips"]
    if require_tpu:
        platform, kind, count = run.device_info(chips)
        run.enable_compile_cache()
    else:
        d = jax.devices()[0]
        platform, kind, count = d.platform, d.device_kind, len(jax.devices())
    cfg, mix = spec.config, spec.mix
    system = system_lib.build(cfg, seed, buckets=mix.get("buckets"))
    session = system.session
    load = loads.KINDS[mix["kind"]](mix, cfg, system, seed)
    load.prepare(max(seconds, run.TRACE_SECONDS))
    load.warm(session)

    def window(seconds, tracer=None):
        obs.set_tracer(tracer)
        gc.collect()
        gc.freeze()
        try:
            with jax.profiler.TraceAnnotation(pr.WINDOW):
                return load.run(session, seconds, 1)
        finally:
            gc.unfreeze()
            obs.set_tracer(None)

    off, on, slowest = [], [], []
    for _ in range(pairs):
        w = window(seconds)
        off.append(w.e2e["ms_per_image"])
        slowest.append({"tracer": False,
                        "call_ms": 1e3 * w.notes["call_s_max"]})
        on_tracer = obs.Tracer()
        w = window(seconds, on_tracer)
        on.append(w.e2e["ms_per_image"])
        slowest.append(dict(slowest_call(on_tracer.spans), tracer=True))
    log_dir = os.path.join(spec.root, ".bench", "phases")
    shutil.rmtree(log_dir, ignore_errors=True)
    tracer = obs.Tracer()
    jax.profiler.start_trace(log_dir)
    traced = window(min(seconds, run.TRACE_SECONDS), tracer)
    jax.profiler.stop_trace()
    t0 = time.perf_counter()
    texts = session.compiled_hlo(mix.get("buckets"))
    phase_map_s = time.perf_counter() - t0
    planes = pr.load_planes(pr.find_xplane(log_dir))
    reduced = reduce_phases(planes, op_scopes(texts),
                            {s.name for s in tracer.spans})
    result = {
        "workload": spec.name, "seed": seed,
        "device": {"platform": platform, "kind": kind, "count": count},
        "ms_per_image_tracer_off": off, "ms_per_image_tracer_on": on,
        "slowest_call_per_window": slowest,
        "traced_images": traced.notes.get("images"),
        "host_ms_per_call": host_ms_per_call(tracer.spans),
        "ms_per_call": ms_per_call(tracer.spans),
        "phase_map_s": phase_map_s,
    }
    if reduced is not None:
        result["scoped_share_pct"] = scoped_share_pct(reduced)
        result.update(reduced)
    if out:
        os.makedirs(out, exist_ok=True)
        tag = f"{spec.name}.{seed}"
        for i, text in enumerate(texts):
            with open(os.path.join(out, f"{tag}.hlo{i}.txt"), "w") as f:
                f.write(text)
        with open(os.path.join(out, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    shutil.rmtree(log_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of each tracer-cost window")
    ap.add_argument("--pairs", type=int, default=2,
                    help="tracer off/on window pairs")
    ap.add_argument("--out", default=None,
                    help="directory for the HLO text and the result")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import run

    result = measure(run.Spec(args.workload), args.seed, args.seconds,
                     args.pairs, out=args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
