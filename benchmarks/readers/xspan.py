"""Reader ``xspan``: the program's own spans as the profiler recorded them.

The program holds a ``jax.profiler.TraceAnnotation("ktpu.<name>")`` open
around each phase (``PhaseAccumulator.span``), so in a traced run the
spans sit in the trace's host plane, on the thread that did the work and on
the SAME clock as the device's ops.  This reader loads the run's own
``*.xplane.pb`` (the newest under ``runner.TRACE_DIR``), takes the window
from the ``bench_window`` marker (its start is the window's opening; its
length is the reduced trace's ``window_s`` where there is one, else the
marker's own) and reads ``ktpu.*`` events by name.  One clock, the
profiler's: nothing here is joined with the host's.

``params.spans`` names the events; ``params.what`` is one of

* ``sum_s``          — summed seconds of the named spans inside the window
                       (over all threads);
* ``union_s``        — seconds of the window covered by at least one of them;
* ``after_last_s``   — window close minus the end of the last of them;
* ``idle_overlap_s`` — seconds in which the device ran no op (complement of
                       the union of ``XLA Ops``, per device plane, averaged)
                       AND one of the named spans was open.

All per 1,000 pods bound in the window.  ``None`` (metric left out) where
the trace has no marker, none of the named spans (a program without them),
or, for ``idle_overlap_s``, no device plane.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import runner, trace_reduce

PREFIX = "ktpu."
_KV_TAIL = re.compile(r"#[^#]*#$")  # TraceMe's "#k=v,k=v#" metadata tail

Interval = Tuple[float, float]


def span_name(event_name: str) -> str:
    return _KV_TAIL.sub("", event_name)


def collect(planes) -> dict:
    """``planes`` as ``ProfileData`` gives them (the tests hand in plain
    stand-ins): the marker, every ``ktpu.*`` span by name, and the op
    intervals of each device plane, all in trace nanoseconds."""
    marker = None
    spans: Dict[str, List[Interval]] = {}
    ops: Dict[str, List[Interval]] = {}
    for plane in planes:
        dev = trace_reduce.is_device_plane(plane.name)
        for line in plane.lines:
            if dev:
                if line.name == trace_reduce.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                        for e in line.events
                    )
                continue
            for e in line.events:
                name = span_name(e.name)
                if name == trace_reduce.MARKER:
                    marker = (float(e.start_ns), float(e.duration_ns))
                elif name.startswith(PREFIX):
                    a = float(e.start_ns)
                    spans.setdefault(name, []).append((a, a + float(e.duration_ns)))
    return {"marker": marker, "spans": spans, "ops": {k: v for k, v in ops.items() if v}}


def window_of(col: dict, window_s: Optional[float]) -> Optional[Interval]:
    if col["marker"] is None:
        return None
    w0, dur = col["marker"]
    return (w0, w0 + (window_s * 1e9 if window_s else dur))


def clip(intervals: Sequence[Interval], w: Interval) -> List[Interval]:
    return [(max(a, w[0]), min(b, w[1])) for a, b in intervals if b > w[0] and a < w[1]]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def measure(col: dict, w: Interval, what: str, names: Sequence[str]) -> Optional[float]:
    """Seconds, or None where there is nothing to read."""
    found = [iv for n in names for iv in col["spans"].get(n, ())]
    if not found:
        return None
    inside = clip(found, w)
    if what == "sum_s":
        return length(inside) * 1e-9
    if what == "union_s":
        return length(trace_reduce.union(inside)) * 1e-9
    if what == "after_last_s":
        return (w[1] - max(b for _a, b in inside)) * 1e-9 if inside else None
    if what == "idle_overlap_s":
        if not col["ops"]:
            return None
        covered = trace_reduce.union(inside)
        each = []
        for ops in col["ops"].values():
            idle = trace_reduce.gaps(trace_reduce.union(clip(ops, w)), w[0], w[1])
            each.append(length(intersect(idle, covered)) * 1e-9)
        return sum(each) / len(each)
    raise ValueError(f"xspan reader: unknown what={what!r}")


def table(col: dict, w: Interval) -> List[Tuple[str, int, float, float]]:
    """(name, events, summed seconds, union seconds) of every ``ktpu.*`` span
    inside the window, largest sum first."""
    rows = []
    for name, ivs in col["spans"].items():
        inside = clip(ivs, w)
        if inside:
            rows.append((name, len(inside), length(inside) * 1e-9,
                         length(trace_reduce.union(inside)) * 1e-9))
    return sorted(rows, key=lambda r: -r[2])


def load(ctx: dict) -> Optional[dict]:
    """The run's trace, collected once per run (kept in ``ctx``: each metric
    loads this file anew) with its table printed once.  Beside what
    ``collect`` gives it holds ``path``, ``planes`` and ``window`` (None
    without the marker); the ``scope`` reader starts from it too."""
    if "_xspan" in ctx:
        return ctx["_xspan"]
    ctx["_xspan"] = None
    paths = sorted(glob.glob(os.path.join(runner.TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    planes = list(trace_reduce.load(paths[-1]).planes)
    col = collect(planes)
    col.update(path=paths[-1], planes=planes,
               window=window_of(col, (ctx.get("trace") or {}).get("window_s")))
    ctx["_xspan"] = col
    w = col["window"]
    if w is None:
        runner.say("xspan: no bench_window marker in the trace")
        return col
    runner.say(f"xspan: window {(w[1] - w[0]) * 1e-9:.4f}s on the profiler's clock, "
               f"{len(col['ops'])} device plane(s); span, events, sum s, union s:")
    for name, n, total, uni in table(col, w):
        runner.say(f"xspan   {name:<34} {n:>6} {total:>10.4f} {uni:>10.4f}")
    return col


def read(ctx: dict, params: dict):
    kpods = ctx.get("pods_in_window", 0) / 1000.0
    if kpods <= 0:
        return None
    col = load(ctx)
    if col is None or col["window"] is None:
        return None
    secs = measure(col, col["window"], params["what"], params["spans"])
    return None if secs is None else secs / kpods
