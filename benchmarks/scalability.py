"""Paper Fig 5 + Table 6: batch-search scalability with cluster size.

Two parts:
 1. measured: wall time vs shard count over the devices this process
    holds (1, 2, 4, 8 as far as they go), each row naming the device;
 2. modelled: the roofline terms from the dry-run give T(N) = max(compute/N,
    memory/N, collective(N)); we report the projected 10 -> 100 chip
    speedup for the search cell next to the paper's measured 7.2x.
"""

from __future__ import annotations

import time

from benchmarks.common import row


def _search_seconds(n_devices: int) -> float:
    """Mean wall time of one batch search over a ``data=n_devices`` mesh
    of this process's first devices (compile excluded)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.index_build import build_index
    from repro.core.search import batch_search
    from repro.core.tree import build_tree
    from repro.data import synth

    devs = np.array(jax.devices()[:n_devices]).reshape(n_devices, 1)
    mesh = Mesh(devs, ("data", "model"))
    vecs_np, _ = synth.sample_descriptors(60000, 32, seed=0, n_centers=256)
    vecs = jnp.asarray(vecs_np)
    tree = build_tree(vecs, (16, 16), key=jax.random.PRNGKey(1))
    index = build_index(vecs, tree, mesh)
    q = vecs[:2048]
    r = batch_search(index, tree, q, k=5, mesh=mesh, q_cap=1024)  # compile
    jax.block_until_ready(r.ids)
    t0 = time.perf_counter()
    for _ in range(3):
        r = batch_search(index, tree, q, k=5, mesh=mesh, q_cap=1024)
        jax.block_until_ready(r.ids)
    return (time.perf_counter() - t0) / 3


def run():
    """Shard sweep over the devices this process holds (1, 2, 4, 8 as
    far as they go), all in this one process."""
    import jax

    out = []
    base = None
    kind = jax.devices()[0].device_kind
    for n in (1, 2, 4, 8):
        if n > len(jax.devices()):
            break
        t = _search_seconds(n)
        base = base or t
        out.append(row(f"fig5_shards_{n}", t,
                       f"rel={base / t:.2f}x on {n} x {kind}"))
    # modelled speedup from the dry-run roofline (see EXPERIMENTS.md §Roofline)
    import json
    import os

    if os.path.exists("dryrun_results.jsonl"):
        recs = [json.loads(l) for l in open("dryrun_results.jsonl")]
        for r in recs:
            if (r["arch"], r["shape"], r["mesh"], r.get("status")) == (
                "sift100m", "search_1m", "16x16", "ok",
            ):
                ro = r["roofline"]
                # terms scale 1/N except a ~log collective share
                def t_of(n):
                    return max(
                        ro["t_compute"] * 256 / n,
                        ro["t_memory"] * 256 / n,
                        ro["t_collective"] * 256 / n * 1.5,
                    )

                speedup = t_of(10) / t_of(100)
                out.append(
                    row(
                        "fig5_modelled_10_to_100_chips", 0.0,
                        f"projected={speedup:.1f}x vs paper 7.2x",
                    )
                )
                break
    return out
