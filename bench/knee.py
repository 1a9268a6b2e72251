"""Find the knee of an open-loop cell: the highest rate it sustains.

    python bench/knee.py --config copydays-sift --traffic online --seed 9 \
        --seconds 30 --rates 4 5 6 7 8

It needs no cell in ``BENCHMARK.json``: the rate is found before the cell
is listed. One process on one chip, one set-up; the mix is run at each
rate for ``--seconds`` and the line per rate says how many requests were
due, how long the queue took to drain after the window closed, and the
latency.
A rate is sustained while the drain stays within about one dispatch. The
knee found is written into the mix file's ``rate`` at 0.8 x by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import loads
    import run
    import system as system_lib

    cfg = run.load_json(os.path.join(HERE, "configs", args.config + ".json"))
    base = run.load_json(os.path.join(HERE, "traffic", args.traffic + ".json"))
    run.device_info(1)
    run.enable_compile_cache()
    system = system_lib.build(cfg, args.seed, buckets=base.get("buckets"))
    for rate in args.rates:
        mix = dict(base, rate=rate)
        load = loads.KINDS[mix["kind"]](mix, cfg, system, args.seed)
        load.prepare(args.seconds)
        load.warm(system.session)
        w = load.run(system.session, args.seconds, 0)
        dsp = w.online["dispatches"]
        fill = sum(d["rows"] for d in dsp) / max(1, sum(d["bucket"]
                                                      for d in dsp))
        print(json.dumps(dict(rate=rate, failed=w.failed,
                              mean_fill=fill, **w.notes)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
