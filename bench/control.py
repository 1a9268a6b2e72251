"""The control of each cell's comparison, at the cell's own size.

    python bench/control.py --workload <cell> --seeds 5 6 7

For each seed it makes the cell's data and tree, takes the query rows a run
of the cell compares (the mix's own calls or requests), puts the plain
reference computed in bfloat16 (``Reference.control_knn``) in the
program's place, and prints the compared numbers, which must fail the
configuration's checks. It also prints the exact reference compared with
itself, which must pass. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class _Data:
    def __init__(self, corpus):
        self.corpus = corpus


def queries(spec, seed: int, n: int, corpus):
    """Up to ``n`` query rows of the kind a run of the cell compares."""
    import numpy as np

    import loads

    load = loads.KINDS[spec.mix["kind"]](spec.mix, spec.config,
                                         _Data(corpus), seed)
    if hasattr(load, "call"):
        rows, i = [], 0
        while sum(len(r) for r in rows) < n:
            rows.append(load.call(i)[0])
            i += 1
        return np.concatenate(rows)[:n]
    reqs = load.requests(30.0)
    return np.concatenate([r.queries for r in reqs])[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=8192)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import run
    import system as system_lib
    from reference import Reference, verdict

    spec = run.Spec(args.workload)
    cfg = spec.config
    for seed in args.seeds:
        t0 = time.perf_counter()
        corpus, levels = system_lib.make_data(cfg, seed)
        q = queries(spec, seed, args.rows, corpus)
        ref = Reference(corpus, levels, cfg["search"]["k"])
        out = {"workload": args.workload, "seed": seed, "rows": len(q)}
        for name, answers in (("control_bf16", ref.control_knn(q)),
                              ("reference", ref.knn(q)[1:])):
            nums = ref.compare(q, *answers)
            nums["unanswered"] = 0
            correct, checks = verdict(nums, cfg["checks"])
            out[name] = dict(nums, correct=correct)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
