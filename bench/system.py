"""The system under test of one configuration, built from the seed through
the program's normal path: ``Index.create -> append -> commit``, then a
warmed ``SearchSession``.

The index is ephemeral (``Index.create(tree, None)``): the same lifecycle
with nothing written to disk, so a run writes no 4 GB segment.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

import corpus as corpus_lib


@dataclasses.dataclass
class System:
    corpus: np.ndarray  # (n, d) base rows on the host; row i has id i
    levels: list  # the tree as numpy, [(f0, d), (f0, f1, d)]
    index: object
    session: object
    timings: dict


def make_data(cfg: dict, seed: int):
    """``(corpus, levels)`` on the host."""
    data = cfg["data"]
    base = corpus_lib.make_descriptors(
        seed, data["n_images"] * data["desc_per_image"], data["dim"],
        data["n_centers"])
    corpus = np.asarray(base)
    del base
    tree = cfg["tree"]
    levels = corpus_lib.make_tree(corpus, tree["fanouts"], tree["sample"],
                                  seed)
    return corpus, levels


def build(cfg: dict, seed: int, buckets=None) -> System:
    import jax.numpy as jnp

    from repro.core.tree import VocabTree
    from repro.index import Index
    from repro.serving import SearchSession

    t = {}
    t0 = time.perf_counter()
    corpus, levels = make_data(cfg, seed)
    t["data_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    tree = VocabTree(levels=tuple(jnp.asarray(lvl) for lvl in levels))
    index = Index.create(tree, None)
    index.append(corpus)
    index.commit()
    t["build_s"] = time.perf_counter() - t0
    s = cfg["search"]
    session = SearchSession(
        index, k=s["k"], probes=s["probes"], layout=s["layout"],
        impl=s["impl"], buckets=buckets)
    t0 = time.perf_counter()
    session.warmup()
    t["warmup_s"] = time.perf_counter() - t0
    return System(corpus, levels, index, session, t)
