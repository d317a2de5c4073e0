"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers a run reports.

- the window: the harness's ``chipbench.window`` host span;
- busy: the union of the intervals in which an operation ran on a device,
  clipped to the window and averaged over the devices that ran anything;
- top device operations by their summed device time;
- idle time, split at the harness's host spans and named by what the host
  was doing over each piece: the ``chipbench.*`` span and the innermost
  Python frame (``$file.py:line function``, the profiler's Python tracer)
  on the same thread;
- every ``chipbench.*`` host span, for the per-layer readers.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW = "chipbench.window"
PREFIX = "chipbench."
OPS_LINES = ("XLA Ops",)  # preferred device line; else every line of the plane


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo: float, hi: float):
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()


def innermost(frames, points):
    """For each of the sorted ``points``, the name of the innermost of the
    properly nested ``frames`` [(start, end, name)] that covers it, or None."""
    frames = sorted(frames, key=lambda f: (f[0], -f[1]))
    out, stack, j = [], [], 0
    for q in points:
        while j < len(frames) and frames[j][0] <= q:
            while stack and stack[-1][1] < frames[j][0]:
                stack.pop()
            stack.append(frames[j])
            j += 1
        while stack and stack[-1][1] < q:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def reduce_events(host_spans, device_events, host_frames=None) -> dict:
    """The reduction on plain data, in nanoseconds.

    ``host_spans``: [(name, start, end)] of the harness's annotations;
    ``device_events``: {device: [(op name, start, end)]};
    ``host_frames``: [(start, end, name)] of the Python frames on the
    harness's thread, if the trace has them.
    """
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    busy_per_dev, op_time = [], defaultdict(float)
    busy0 = None
    for dev in sorted(device_events):
        evs = clip([(s, e) for _, s, e in device_events[dev]], lo, hi)
        if not evs:
            continue
        merged = union(evs)
        busy_per_dev.append(sum(e - s for s, e in merged))
        if busy0 is None:
            busy0 = merged
        for name, s, e in device_events[dev]:
            if e > lo and s < hi:
                op_time[name] += min(e, hi) - max(s, lo)
    # the harness's spans inside the window run one after another on one thread
    inner = sorted((s, e, n) for n, s, e in host_spans if n != WINDOW)
    starts = [s for s, _, _ in inner]
    edges = sorted({t for s, e, _ in inner for t in (s, e)})
    pieces = []
    for s, e in gaps(busy0 or [], lo, hi):
        cuts = [s] + edges[bisect.bisect_right(edges, s):bisect.bisect_left(edges, e)] + [e]
        pieces += [(0.5 * (a + b), b - a) for a, b in zip(cuts[:-1], cuts[1:])]
    frames = innermost(host_frames or [], [mid for mid, _ in pieces])
    idle = defaultdict(float)
    for (mid, length), frame in zip(pieces, frames):
        i = bisect.bisect_right(starts, mid) - 1
        name = inner[i][2] if i >= 0 and inner[i][1] >= mid else "outside chipbench spans"
        idle[f"{name} > {frame}" if frame else name] += length
    spans = defaultdict(list)
    for n, s, e in host_spans:
        spans[n].append((s, e))
    n_dev = max(len(busy_per_dev), 1)
    return {
        "window_ns": hi - lo,
        "window": (lo, hi),
        "busy_ns": sum(busy_per_dev) / n_dev,
        "busy_intervals": busy0 or [],
        "devices_busy": len(busy_per_dev),
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1]),
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1]),
        "spans": dict(spans),
    }


def reduce_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` with JAX's own reader and reduce it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_spans, host_frames, device_events = [], [], {}
    for plane in data.planes:
        if _is_device_plane(plane.name):
            lines = list(plane.lines)
            chosen = [ln for ln in lines if ln.name in OPS_LINES] or lines
            device_events[plane.name] = [
                (ev.name.split(" = ", 1)[0], ev.start_ns, ev.start_ns + ev.duration_ns)
                for ln in chosen for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in ln.events]
                ours = [e for e in events if e[0].startswith(PREFIX)]
                if ours:  # the harness's thread: its spans and its Python frames
                    host_spans += ours
                    host_frames += [(s, e, n) for n, s, e in events if n.startswith("$")]
    return reduce_events(host_spans, device_events, host_frames)


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: [name, seconds] lists, longest first."""
    return {
        "device_ops": [[n, t * 1e-9] for n, t in red["device_ops"][:top]],
        "idle_gaps": [[n, t * 1e-9] for n, t in red["idle_gaps"][:top]],
    }
