"""Record the small profiler trace that ``test_bench_reduce.py`` reads.

    python bench/tests/record_trace.py <out_dir>

Run on a TPU; it writes ``<out_dir>/reduce_trace.xplane.pb`` and prints the
trace's planes and lines. Copy the file to ``bench/tests/data/`` to renew
the test's data.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import profile_reduce  # noqa: E402


def main():
    out_dir = sys.argv[1]
    out = os.path.join(out_dir, "reduce_trace")
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.pace"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    path = profile_reduce.find_xplane(out)
    shutil.copy(path, os.path.join(out_dir, "reduce_trace.xplane.pb"))
    for name, lines in profile_reduce.load_planes(path):
        print(name, [(ln, len(evs), evs[:2]) for ln, evs in lines])
    print(profile_reduce.reduce_planes(profile_reduce.load_planes(path)))
    print(glob.glob(out + "/**", recursive=True))


if __name__ == "__main__":
    main()
