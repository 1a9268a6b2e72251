"""Traffic generators, one per ``kind`` of a mix file (``traffic/<mix>.json``).

Each takes the mix's parameters, the configuration and the seed, makes its
requests in set-up, warms the session on them, drives one measured window
and hands back what was answered, for the metrics and the comparison.
The images' popularity and arrival process follow the program's own
generators (``repro.data.synth.sample_trace``, ``repro.serving.trace``):
uniform or Zipf(s) image ids with popularity ranks shuffled over the ids,
Poisson arrivals.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

import corpus as corpus_lib


@dataclasses.dataclass
class Window:
    seconds: float  # wall time of the window
    attempted: int  # units of work due in the window
    failed: int  # units that never got an answer
    e2e: dict  # end-to-end metrics this kind measures
    queries: np.ndarray  # (n, d) rows whose answers are compared
    ids: np.ndarray  # (n, k) answers to those rows
    dists: np.ndarray
    dispatch_queries: list  # the query rows of every dispatch, in order
    notes: dict = dataclasses.field(default_factory=dict)
    online: dict | None = None  # the open loop's own records


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def image_ids(rng, n: int, n_images: int, popularity: str, zipf_s: float):
    if popularity == "uniform":
        return rng.integers(0, n_images, n)
    if popularity != "zipf":
        raise ValueError(f"unknown popularity {popularity!r}")
    w = 1.0 / np.arange(1, n_images + 1, dtype=np.float64) ** zipf_s
    p = (w / w.sum())[rng.permutation(n_images)]
    return rng.choice(n_images, size=n, p=p)


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (``inf`` entries sort last)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, int(np.ceil(q * len(v))) - 1)])


class ClosedImages:
    """Closed loop: one client, each call sent when the last returns, with
    ``images_per_call`` whole query images per ``search`` call, images
    drawn by ``popularity``; ``pool_calls`` calls made in set-up and sent
    in turn."""

    def __init__(self, mix, cfg, system, seed):
        self.mix, self.cfg, self.system, self.seed = mix, cfg, system, seed
        data = cfg["data"]
        rng = rng_of(seed, 1)
        self.pool = []
        for _ in range(mix["pool_calls"]):
            imgs = image_ids(rng, mix["images_per_call"], data["n_images"],
                             mix["popularity"], mix.get("zipf_s", 1.1))
            self.pool.append((corpus_lib.image_queries(
                system.corpus, imgs, data["desc_per_image"], mix["noise"],
                seed), len(imgs)))

    def call(self, i):
        return self.pool[i % len(self.pool)]

    def prepare(self, seconds: float) -> None:
        """Calls are made in set-up already (``__init__``)."""

    def warm(self, session):
        q, n_img = self.call(0)
        session.search(q, n_images=n_img)

    def run(self, session, seconds: float, sample_rows: int) -> Window:
        done, spans = [], []
        t0 = time.perf_counter()
        while True:
            q, n_img = self.call(len(done))
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.call"):
                ids, dists = session.search(q, n_images=n_img)
            done.append((q, ids, dists, n_img))
            spans.append(time.perf_counter() - t1)
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        rows = sum(len(q) for q, *_ in done)
        images = sum(n for *_, n in done)
        queries = np.concatenate([q for q, *_ in done])
        ids = np.concatenate([i for _, i, _, _ in done])
        dists = np.concatenate([d for _, _, d, _ in done])
        pick = np.sort(rng_of(self.seed, 11).choice(
            len(queries), min(sample_rows, len(queries)), replace=False))
        return Window(
            seconds=wall, attempted=images, failed=0,
            e2e={"ms_per_image": wall * 1e3 / images},
            queries=queries[pick], ids=ids[pick], dists=dists[pick],
            dispatch_queries=[q for q, *_ in done],
            notes={"calls": len(done), "rows": rows, "images": images,
                   "call_s_max": max(spans),
                   "call_s_median": float(np.median(spans))},
        )


class OpenImages:
    """Open loop: one image per request, Poisson arrivals at ``rate``
    requests/s, image ids by ``popularity``, through the program's
    ``MicroBatcher`` paced on the wall clock (``online.py``).

    Every seed sends the same arrival schedule (Poisson gaps drawn from
    the mix's ``gap_seed``), so the load, and the queueing it causes, are
    the same in every run; the seed picks the images."""

    def __init__(self, mix, cfg, system, seed):
        self.mix, self.cfg, self.system, self.seed = mix, cfg, system, seed

    def prepare(self, seconds: float) -> None:
        self.reqs = self.requests(seconds)

    def requests(self, seconds: float):
        from repro.serving.trace import Request

        m, data = self.mix, self.cfg["data"]
        gaps = np.random.default_rng(m["gap_seed"]).exponential(
            1.0 / m["rate"], int(4 * m["rate"] * seconds) + 16)
        arrivals = np.cumsum(gaps)
        arrivals = arrivals[arrivals < seconds]
        n = len(arrivals)
        rng = rng_of(self.seed, 2)
        imgs = image_ids(rng, n, data["n_images"], m["popularity"],
                         m.get("zipf_s", 1.1))
        q = corpus_lib.image_queries(self.system.corpus, imgs,
                                     data["desc_per_image"], m["noise"],
                                     self.seed)
        dpi = data["desc_per_image"]
        return [Request(rid=i, image_id=int(img), arrival=float(t),
                        queries=q[i * dpi:(i + 1) * dpi])
                for i, (img, t) in enumerate(zip(imgs, arrivals))]

    def warm(self, session):
        """``session.warmup()`` compiled every rung on empty batches; run
        each once more on real requests that fill it, as the window will."""
        dpi = self.cfg["data"]["desc_per_image"]
        reqs = self.requests(2.0 * session.buckets[-1] / dpi / self.mix["rate"])
        for bucket in session.buckets:
            n = max(1, bucket // dpi)
            session.serve_many([reqs[i % len(reqs)].queries
                                for i in range(n)])

    def run(self, session, seconds: float, sample_rows: int) -> Window:
        from online import GiveUp, WallClockBatcher

        m, reqs = self.mix, self.reqs
        t0 = time.perf_counter()
        batcher = WallClockBatcher(
            session, t0=t0, give_up_s=seconds + m["wait_s"],
            max_wait_ms=m["max_wait_ms"], max_queue=m["max_queue"],
            scheduler=m["scheduler"])
        try:
            batcher.run(reqs)
        except GiveUp:
            pass
        wall = time.perf_counter() - t0
        done = {c.rid: c for c in (batcher.done or [])
                if c.source in ("engine", "cache")}
        lat = [(done[r.rid].finish - r.arrival) * 1e3 if r.rid in done
               else np.inf for r in reqs]
        failed = len(reqs) - len(done)
        dsp = batcher.dispatches
        dpi = self.cfg["data"]["desc_per_image"]
        answered = sorted(done)
        pick = np.sort(rng_of(self.seed, 12).choice(
            len(answered), min(len(answered), max(1, sample_rows // dpi)),
            replace=False)) if answered else np.zeros(0, np.int64)
        rids = [answered[i] for i in pick]
        by_rid = {r.rid: r for r in reqs}
        cat = (lambda xs: np.concatenate(xs) if xs else np.zeros((0, 0)))
        late = [max(0.0, d["start"] - d["due"]) * 1e3 for d in dsp]
        return Window(
            seconds=wall, attempted=len(reqs), failed=failed,
            e2e={"p95_ms": nearest_rank(lat, 0.95)},
            queries=cat([by_rid[i].queries for i in rids]),
            ids=cat([done[i].ids for i in rids]),
            dists=cat([done[i].dists for i in rids]),
            dispatch_queries=[
                np.concatenate([by_rid[i].queries for i in d["rids"]])
                for d in dsp],
            notes={"requests": len(reqs), "answered": len(done),
                   "p50_ms": nearest_rank(lat, 0.5),
                   "p95_ms": nearest_rank(lat, 0.95),
                   "dispatches": len(dsp),
                   "pacing_late_ms_p50": nearest_rank(late, 0.5)
                   if late else None,
                   "pacing_late_ms_max": max(late) if late else None,
                   "dispatch_s_by_bucket": {
                       b: [round(d["end"] - d["start"], 3) for d in dsp
                           if d["bucket"] == b]
                       for b in sorted({d["bucket"] for d in dsp})},
                   "drain_s": wall - seconds},
            online={"requests": reqs, "done": done, "dispatches": dsp},
        )


KINDS = {
    "closed_images": ClosedImages,
    "open_images": OpenImages,
}
