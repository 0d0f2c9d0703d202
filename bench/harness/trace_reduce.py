"""Reduce a profiler trace (``.xplane.pb``) to device busy time and op times.

The reduction reads nothing but the trace: the device planes'
``XLA Ops`` lines (one event per executed HLO op, with its device start
and duration; a ``while`` op's event encloses those of its body, so
per-op totals are inclusive and only the union of intervals is busy
time) and the host spans the benchmark writes with
``jax.profiler.TraceAnnotation`` (``bench.window`` around the traced steps,
``Trainer.run`` around each call into the program).  The window is the
``bench.window`` span; device events are clipped to it.

``events_of(path)`` flattens a trace into plain lists, so a test can run
the reduction on a small recorded trace without JAX's reader.
``reduce_events`` turns them into the dict that the per-layer metric
readers take their numbers from, and ``chip_ops`` into the first chip's
timeline, for the readers that need to know what ran at the same time.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def find_trace(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event (``fusion.12``): TPU
    traces name each op by its whole instruction text
    (``%fusion.12 = bf16[...] fusion(...)``)."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def events_of(path: str) -> dict:
    """Flatten an xplane file: device op events and host spans.

    Returns ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``, host spans being the
    events of every host line.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[op_name(ev.name), ev.start_ns, ev.duration_ns]
                            for ev in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, ev.start_ns, ev.duration_ns]
                         for ev in line.events]
    return {"devices": devices, "host": host}


def save_events(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(events: dict, *, window_span: str = WINDOW_SPAN,
                  top: int = 10) -> dict:
    """Busy time, per-op totals and idle gaps inside the window.

    Returns ``window_s``; ``busy_s`` averaged over the device planes;
    ``op_s`` (op name -> seconds, summed over devices and divided by the
    device count, so per chip); ``chips``; and ``idle_gaps``: the longest
    gaps of the first device, each named by the innermost host span that
    covers its midpoint.
    """
    spans = [h for h in events["host"] if h[0] == window_span]
    if not spans:
        raise ValueError(f"trace holds no {window_span!r} span")
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    window_ns = w1 - w0
    planes = sorted(events["devices"])
    if not planes:
        raise ValueError("trace holds no device plane")
    busy, op_ns, first_gaps = [], {}, []
    for i, plane in enumerate(planes):
        ivs = []
        for name, s, d in events["devices"][plane]:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            op_ns[name] = op_ns.get(name, 0) + (b - a)
        merged = _union(ivs)
        busy.append(sum(b - a for a, b in merged))
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            first_gaps = [(edges[k], edges[k + 1])
                          for k in range(0, len(edges), 2)
                          if edges[k + 1] > edges[k]]
    n = len(planes)
    host = [h for h in events["host"] if h[0] != window_span]
    gaps = []
    for a, b in sorted(first_gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = [h for h in host if h[1] <= mid <= h[1] + h[2]]
        name = min(cover, key=lambda h: h[2])[0] if cover else "no host span"
        gaps.append([name, (b - a) / 1e9])
    return {"window_s": window_ns / 1e9,
            "busy_s": sum(busy) / n / 1e9,
            "chips": n,
            "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
            "idle_gaps": gaps}


def chip_ops(events: dict, *, window_span: str = WINDOW_SPAN) -> list:
    """The first device's op events inside the window, in order of start,
    as ``[name, start_ns, end_ns]`` clipped to the window: the timeline on
    which a reader can tell what ran at the same time as what."""
    spans = [h for h in events["host"] if h[0] == window_span]
    if not spans or not events["devices"]:
        return []
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    first = sorted(events["devices"])[0]
    out = [[name, max(s, w0), min(s + d, w1)]
           for name, s, d in events["devices"][first]]
    return sorted((e for e in out if e[2] > e[1]), key=lambda e: e[1])
