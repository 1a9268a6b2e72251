"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy time inside the measured window, the device
operations that took most time, and the longest idle gaps, each named by the
harness annotation (``jax.profiler.TraceAnnotation``) the host was inside.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run. Host annotations are the ``bench.*``
events of the ``/host:CPU`` plane. Both are on the profiler's one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
TOP = 10


@dataclasses.dataclass
class Reduced:
    busy_s: float  # device-busy seconds inside the window, mean over chips
    window_s: float  # length of the traced window
    device_ops: list  # [[op name, seconds]] most time first, at most TOP
    idle_gaps: list  # [[host annotation, seconds]] longest first, at most TOP
    n_devices: int


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_planes(planes) -> Reduced | None:
    """``planes``: ``[(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]``. ``None`` when the trace holds no window or no
    device operation inside it."""
    host = []
    devices = []
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            devices.append([ev for lname, evs in lines if lname == OPS_LINE
                            for ev in evs])
        elif pname == "/host:CPU":
            host.extend(ev for _, evs in lines for ev in evs
                        if ev[0].startswith("bench."))
    windows = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    busy, per_op = [], {}
    gaps = []
    annotations = sorted(((s, s + d, n) for n, s, d in host if n != WINDOW),
                         key=lambda a: (a[0], -a[1]))
    for ops in devices:
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op = name.split(" = ")[0]  # "%fusion.3 = f32[...] ..."
                per_op[op] = per_op.get(op, 0.0) + (b - a) * 1e-9
        if not clipped:
            continue
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    if not busy:
        return None
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(
        busy_s=sum(busy) / len(busy),
        window_s=(w1 - w0) * 1e-9,
        device_ops=[[n, s] for n, s in ops],
        idle_gaps=[[_doing(annotations, mid), g * 1e-9]
                   for g, mid in gaps[:TOP]],
        n_devices=len(busy),
    )


def _doing(annotations, t) -> str:
    """The innermost (latest-starting) harness annotation covering ``t``."""
    name = "outside any bench annotation"
    for s, e, n in annotations:
        if s > t:
            break
        if e >= t:
            name = n
    return name


def load_planes(xplane: str):
    """The trace as plain tuples (see :func:`reduce_planes`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    return [
        (plane.name, [
            (line.name, [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                         for ev in line.events])
            for line in plane.lines
        ])
        for plane in pd.planes
    ]


def reduce_dir(log_dir: str) -> Reduced | None:
    return reduce_planes(load_planes(find_xplane(log_dir)))


def idle_pct(run):
    """Share of the traced window in which no operation ran on the device
    (``None`` without a trace)."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
