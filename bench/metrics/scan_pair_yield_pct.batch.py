"""Share of the distance pairs the scan's tiles evaluated that were
same-leaf pairs the search needed: the process-wide registry counters
``engine.pairs_useful`` over ``engine.pairs_computed``, which every
``SearchSession`` dispatch of the run feeds (the warm call and the
window's calls)."""

from repro.obs import get_registry


def read(run):
    counters = get_registry().snapshot()["metrics"]
    computed = counters.get("engine.pairs_computed")
    if not computed:
        return None
    return 100.0 * counters.get("engine.pairs_useful", 0) / computed
