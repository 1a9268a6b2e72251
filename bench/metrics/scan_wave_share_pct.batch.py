"""Share of the scan's point tiles that its sweeps visited: the
process-wide registry counters ``engine.waves_scanned`` over
``engine.waves_total``, which every ``SearchSession`` dispatch of the run
feeds (the warm call and the window's calls). A program without them
reads nothing."""

from repro.obs import get_registry


def read(run):
    counters = get_registry().snapshot()["metrics"]
    total = counters.get("engine.waves_total")
    if not total:
        return None
    return 100.0 * counters.get("engine.waves_scanned", 0) / total
