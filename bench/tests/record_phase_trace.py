"""Record the small profiler trace that ``test_bench_phases.py`` reads: a
program whose ops sit in ``repro.*`` named scopes inside a ``fori_loop``,
called under enabled ``repro.obs.Tracer`` spans.

    python bench/tests/record_phase_trace.py <out_dir>

Run on a TPU; it writes ``<out_dir>/phase_trace.xplane.pb`` and the
program's optimized HLO, ``<out_dir>/phase_trace.hlo.txt``, and prints how
the trace's op names meet the HLO's instructions and what
``phases.reduce_phases`` makes of it. Copy both files to
``bench/tests/data/`` to renew the test's data.
"""

import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import phases  # noqa: E402
import profile_reduce  # noqa: E402
from repro import obs  # noqa: E402


def step(x):
    def body(i, c):
        with jax.named_scope("repro.scan.distance"):
            d = jnp.tanh(c @ x)
        with jax.named_scope("repro.scan.select"):
            v, _ = jax.lax.top_k(d, 8)
        with jax.named_scope("repro.scan.carry"):
            return c + 1e-6 * v.sum(axis=1, keepdims=True)

    return jax.lax.fori_loop(0, 4, body, x).sum()


def main():
    out_dir = sys.argv[1]
    out = os.path.join(out_dir, "phase_trace")
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(step)
    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    text = f.lower(x).compile().as_text()
    f(x).block_until_ready()
    tracer = obs.Tracer()
    jax.profiler.start_trace(out)
    with obs.tracing(tracer), jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                with tracer.span("engine.execute"):
                    with tracer.span("session.wait"):
                        f(x).block_until_ready()
                    with tracer.span("session.record"):
                        time.sleep(0.005)
    jax.profiler.stop_trace()
    path = profile_reduce.find_xplane(out)
    shutil.copy(path, os.path.join(out_dir, "phase_trace.xplane.pb"))
    with open(os.path.join(out_dir, "phase_trace.hlo.txt"), "w") as fh:
        fh.write(text)
    planes = profile_reduce.load_planes(path)
    scopes = phases.op_scopes([text])
    names = {ins for tables in scopes.values() for t in tables for ins in t}
    for name, lines in planes:
        for ln, evs in lines:
            if ln == profile_reduce.OPS_LINE:
                ops = {e[0].split(" = ")[0] for e in evs}
                print(name, "ops", sorted(ops))
                print("not in the HLO text:",
                      sorted(o for o in ops if o.lstrip("%") not in names))
            if ln == phases.MODULES_LINE:
                print(name, "modules", sorted({e[0] for e in evs}))
    print("HLO modules", sorted(scopes))
    print(phases.reduce_phases(planes, scopes,
                               {s.name for s in tracer.spans}))


if __name__ == "__main__":
    main()
