"""Span tracing on virtual or wall-clock time, exported as Chrome trace JSON.

The fedsim runtime's whole point is that *time itself* is simulated — a
churn-under-straggler run is a sequence of dispatch / uplink / flush /
crash / recovery episodes on the :class:`repro.fedsim.clock.VirtualClock`.
This module turns those episodes into Chrome trace-event JSON (the
``{"traceEvents": [...]}`` format) viewable in Perfetto (https://ui.perfetto.dev)
or ``chrome://tracing``, so the timeline becomes *readable* instead of a list
of history rows.

Two time bases share one :class:`Tracer`:

- **virtual time** — the schedulers pass explicit ``ts`` seconds from their
  VirtualClock; these land in the ``pid=2`` ("virtual time") track.
- **wall clock** — :meth:`Tracer.span` (a context manager) stamps
  ``time.perf_counter`` relative to the tracer's birth; these land in
  ``pid=1`` ("wall clock").  ``benchmarks/run.py --profile`` wraps every
  bench in such a span.

Event vocabulary (all milliseconds-displayed, microsecond ``ts`` as the
format requires):

- ``begin``/``end`` — a ``ph: "B"``/``"E"`` span pair on one ``(pid, tid)``
  lane.  Pairs must nest per lane; :func:`validate_trace` enforces balance
  and per-pair monotone timestamps (the CI bench-smoke gate).
- ``complete`` — one ``ph: "X"`` event with an explicit duration (used for
  client compute/uplink episodes whose extent is known at emission).
- ``instant`` — ``ph: "i"`` markers (flush, checkpoint, crash, eval).

Determinism: a tracer fed only virtual-time events from the deterministic
fedsim event loop serializes to byte-identical JSON across runs — the
trace-determinism test pins that.

Program spans: :func:`span` marks a step of the program (the fit's
eigensolve, the dispatcher's legs) as a ``jax.profiler.TraceAnnotation``,
which lands in the JAX profiler's trace on the device trace's clock with its
keyword arguments as event stats, and costs about a microsecond when no
profiler session is active.  With a :class:`Tracer` installed it also
records the wall-clock ``B``/``E`` pair of :meth:`Tracer.span`.  Args known
only at the end go in through ``set_metadata`` on what the ``with`` gives.
Every such name is in :data:`SPAN_NAMES`.
"""
from __future__ import annotations

import contextlib
import json
import time

from jax.profiler import TraceAnnotation

PID_WALL = 1
PID_VIRTUAL = 2
_PROCESS_NAMES = {PID_WALL: "wall clock", PID_VIRTUAL: "virtual time"}


class Tracer:
    """Collects trace events; export with :meth:`to_json` / :meth:`write`."""

    def __init__(self):
        self.events: list[dict] = []
        self._wall0 = time.perf_counter()

    # -- low-level emission (explicit timestamps, virtual-time track) --------

    @staticmethod
    def _us(ts_seconds: float) -> float:
        return round(float(ts_seconds) * 1e6, 3)

    def _emit(self, ph: str, name: str, ts: float, *, pid: int, tid: int,
              args: dict | None = None, **extra) -> None:
        ev = {"name": name, "ph": ph, "ts": self._us(ts), "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        ev.update(extra)
        self.events.append(ev)

    def begin(self, name: str, ts: float, *, tid: int = 0, pid: int = PID_VIRTUAL,
              args: dict | None = None) -> None:
        self._emit("B", name, ts, pid=pid, tid=tid, args=args)

    def end(self, name: str, ts: float, *, tid: int = 0, pid: int = PID_VIRTUAL,
            args: dict | None = None) -> None:
        self._emit("E", name, ts, pid=pid, tid=tid, args=args)

    def complete(self, name: str, ts: float, dur: float, *, tid: int = 0,
                 pid: int = PID_VIRTUAL, args: dict | None = None) -> None:
        if dur < 0:
            raise ValueError(f"span {name!r}: negative duration {dur}")
        self._emit("X", name, ts, pid=pid, tid=tid, args=args, dur=self._us(dur))

    def instant(self, name: str, ts: float, *, tid: int = 0, pid: int = PID_VIRTUAL,
                args: dict | None = None) -> None:
        # scope "t": thread-local marker (renders as a tick on the lane)
        self._emit("i", name, ts, pid=pid, tid=tid, args=args, s="t")

    def wall_now(self) -> float:
        """Seconds since this tracer's birth — the wall-clock timestamp base
        explicit emitters (request tracing, admission legs) share with
        :meth:`span`."""
        return time.perf_counter() - self._wall0

    # -- wall-clock spans (context manager; benches / non-sim paths) ---------

    @contextlib.contextmanager
    def span(self, name: str, *, tid: int = 0, args: dict | None = None):
        """Wall-clock ``B``/``E`` pair around a ``with`` block."""
        self.begin(name, time.perf_counter() - self._wall0, tid=tid,
                   pid=PID_WALL, args=args)
        try:
            yield self
        finally:
            self.end(name, time.perf_counter() - self._wall0, tid=tid, pid=PID_WALL)

    # -- export --------------------------------------------------------------

    def trace_events(self) -> list[dict]:
        """All events plus process-name metadata for the two time tracks."""
        pids = {ev["pid"] for ev in self.events}
        meta = [
            {
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0, "ts": 0,
                "args": {"name": _PROCESS_NAMES.get(pid, f"pid {pid}")},
            }
            for pid in sorted(pids)
        ]
        return meta + self.events

    def to_json(self) -> str:
        return json.dumps(
            {"traceEvents": self.trace_events(), "displayTimeUnit": "ms"}
        )

    def write(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())


# -- the process-wide default tracer (None = tracing off) ---------------------

_TRACER: Tracer | None = None


def get_tracer() -> Tracer | None:
    return _TRACER


def set_tracer(tracer: Tracer | None) -> None:
    global _TRACER
    _TRACER = tracer


@contextlib.contextmanager
def use_tracer(tracer: Tracer | None = None):
    """Scoped tracing: installs ``tracer`` (a fresh one when None), yields
    it, restores the previous tracer on exit."""
    t = Tracer() if tracer is None else tracer
    prev = _TRACER
    set_tracer(t)
    try:
        yield t
    finally:
        set_tracer(prev)


# -- program spans (the profiler's trace, and the installed tracer) ----------

# every name :func:`span` is called with, leaves under their parents:
# rf_tca.fit > stats_wait, eigh, and on the host eigensolve cmat_to_host and
# vecs_to_device around its eigh (core/rf_tca);
# serve.call > batch_assembly, padded_dispatch > launch, device_wait, fetch
# (serve/server, serve/dispatcher)
SPAN_NAMES = (
    "rf_tca.fit",
    "rf_tca.stats_wait",
    "rf_tca.cmat_to_host",
    "rf_tca.eigh",
    "rf_tca.vecs_to_device",
    "serve.call",
    "serve.batch_assembly",
    "serve.padded_dispatch",
    "serve.launch",
    "serve.device_wait",
    "serve.fetch",
)


def span(name: str, **args):
    """Context manager marking one step of the program.

    Always a ``jax.profiler.TraceAnnotation(name, **args)``: recorded, with
    ``args`` as its stats, only while a profiler session is active.  With a
    :class:`Tracer` installed, also its wall-clock ``B``/``E`` pair (``args``
    on the ``B`` event).  ``with span(...) as sp`` gives ``sp.set_metadata(
    **more)``, which adds args known only at the end to both.  Call it from
    Python only, never inside a traced function: there it would mark the
    trace, not the run.
    """
    tracer = _TRACER
    if tracer is None:
        return TraceAnnotation(name, **args)
    return _span_twin(tracer, name, args)


class _TwinMetadata:
    """``set_metadata`` of a span with a Tracer installed: the annotation's
    stats and the args of its ``B`` event."""

    __slots__ = ("_ann", "_begin")

    def __init__(self, ann: TraceAnnotation, begin: dict):
        self._ann, self._begin = ann, begin

    def set_metadata(self, **args) -> None:
        self._ann.set_metadata(**args)
        self._begin.setdefault("args", {}).update(args)


@contextlib.contextmanager
def _span_twin(tracer: Tracer, name: str, args: dict):
    with TraceAnnotation(name, **args) as ann, tracer.span(name, args=args or None):
        yield _TwinMetadata(ann, tracer.events[-1])


# -- schema validation (the CI bench-smoke contract) --------------------------

_REQUIRED_KEYS = ("name", "ph", "ts", "pid", "tid")


def validate_trace(events: list[dict]) -> list[str]:
    """Chrome trace-event schema violations (empty list == valid).

    Checks the contract the CI smoke gates: every event carries
    ``name``/``ph``/``ts``/``pid``/``tid``; ``B``/``E`` pairs balance per
    ``(pid, tid)`` lane with monotone (end >= begin) timestamps and matching
    names; ``X`` events carry a non-negative ``dur``.
    """
    errors: list[str] = []
    stacks: dict[tuple, list[dict]] = {}
    for i, ev in enumerate(events):
        missing = [k for k in _REQUIRED_KEYS if k not in ev]
        if missing:
            errors.append(f"event {i}: missing keys {missing}")
            continue
        ph, lane = ev["ph"], (ev["pid"], ev["tid"])
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] != ev["ts"]:
            errors.append(f"event {i} ({ev['name']!r}): bad ts {ev['ts']!r}")
            continue
        if ph == "B":
            stacks.setdefault(lane, []).append(ev)
        elif ph == "E":
            stack = stacks.get(lane)
            if not stack:
                errors.append(f"event {i}: E {ev['name']!r} with no open B on {lane}")
                continue
            b = stack.pop()
            if b["name"] != ev["name"]:
                errors.append(
                    f"event {i}: E {ev['name']!r} closes B {b['name']!r} on {lane}"
                )
            if ev["ts"] < b["ts"]:
                errors.append(
                    f"event {i}: span {ev['name']!r} ends at {ev['ts']} before "
                    f"its begin {b['ts']} (non-monotone pair)"
                )
        elif ph == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                errors.append(f"event {i}: X {ev['name']!r} bad dur {ev.get('dur')!r}")
    for lane, stack in stacks.items():
        for b in stack:
            errors.append(f"unclosed B {b['name']!r} on lane {lane}")
    return errors


_REQUEST_ROOT = "serve.request"
_REQUEST_LEGS = ("serve.queue_wait", "serve.batch_assembly", "serve.padded_dispatch")
_TREE_TOL_US = 0.5  # containment slack: ts are microseconds rounded to 3 dp


def count_request_trees(events: list[dict]) -> int:
    """Complete per-request span trees in ``events`` (the smoke gate).

    A tree is one ``(pid, tid, args.trace_id)`` lane holding a
    ``serve.request`` root ``X`` span plus all three serving legs
    (queue-wait, batch-assembly, padded-dispatch) as ``X`` spans contained
    in the root's interval — the shape :class:`repro.obs.reqtrace.
    RequestTracer` emits on the virtual-time track.
    """
    groups: dict[tuple, list[dict]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        trace_id = (ev.get("args") or {}).get("trace_id")
        if trace_id is None:
            continue
        groups.setdefault((ev.get("pid"), ev.get("tid"), trace_id), []).append(ev)
    trees = 0
    for evs in groups.values():
        roots = [e for e in evs if e.get("name") == _REQUEST_ROOT]
        if not roots:
            continue
        lo = roots[0]["ts"] - _TREE_TOL_US
        hi = roots[0]["ts"] + roots[0].get("dur", 0) + _TREE_TOL_US
        legs = {
            e["name"] for e in evs
            if e.get("name") in _REQUEST_LEGS
            and e["ts"] >= lo and e["ts"] + e.get("dur", 0) <= hi
        }
        if legs.issuperset(_REQUEST_LEGS):
            trees += 1
    return trees


def validate_trace_file(path, *, require_request_trees: int = 0) -> list[str]:
    """Validate an exported trace JSON file (shape + event schema).

    ``require_request_trees > 0`` additionally demands that many complete
    per-request span trees (:func:`count_request_trees`) — the serving
    observability gate on ``trace_obs.json``.
    """
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: {e}"]
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return [f"{path}: no traceEvents array"]
    real = [ev for ev in events if ev.get("ph") != "M"]
    errors = [f"{path}: {msg}" for msg in validate_trace(real)]
    if require_request_trees > 0:
        trees = count_request_trees(real)
        if trees < require_request_trees:
            errors.append(
                f"{path}: {trees} complete request span tree(s), "
                f"need >= {require_request_trees}"
            )
    return errors
