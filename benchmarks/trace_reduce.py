"""From a profiler trace (``*.xplane.pb``) to numbers.

* ``busy_s``: per device plane, the union of the intervals in which an
  operation ran (line ``XLA Ops``), averaged over the device planes that
  ran anything;
* per-module device seconds and event counts (line ``XLA Modules``; the
  trailing ``(id)`` of a module's name is dropped, so ``jit_resident_run``
  stays one name across compilations);
* the idle gaps inside the traced window, longest first, each named by the
  host span (``PhaseTap``: the program's phases on the host clock) that
  covers most of it.  The two clocks are joined by one marker annotation
  the harness writes at a host time it knows.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

MARKER = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_TAIL = re.compile(r"\(\d+\)$")
NAMED_GAPS = 200  # only the longest gaps are matched against host spans


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float) -> List[Tuple[float, float]]:
    """The complement of the (merged, sorted) busy intervals in [t0, t1]."""
    out, at = [], t0
    for a, b in busy:
        if b <= t0:
            continue
        if a >= t1:
            break
        if a > at:
            out.append((at, min(a, t1)))
        at = max(at, b)
    if at < t1:
        out.append((at, t1))
    return out


def module_name(event_name: str) -> str:
    return _ID_TAIL.sub("", event_name.strip())


def op_name(event_name: str) -> str:
    """``%fusion.12 = (u32[...]...) fusion(...)`` -> ``fusion.12``: the
    profiler names a device op by its whole HLO text."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")[:80]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "host" not in name.lower()


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce_planes(planes) -> dict:
    """``planes``: objects with ``.name`` and ``.lines``; lines with
    ``.name`` and ``.events``; events with ``.name``, ``.start_ns``,
    ``.duration_ns`` (what ProfileData gives; the tests hand in plain
    stand-ins)."""
    devices = {}
    marker = None
    for plane in planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                rec = devices.setdefault(plane.name, {"ops": [], "modules": {}})
                for e in line.events:
                    a, d = float(e.start_ns), float(e.duration_ns)
                    if line.name == OPS_LINE:
                        rec["ops"].append((a, a + d, op_name(e.name)))
                    else:
                        m = rec["modules"].setdefault(module_name(e.name), [0.0, 0])
                        m[0] += d * 1e-9
                        m[1] += 1
            elif not dev:
                for e in line.events:
                    if e.name == MARKER:
                        marker = (float(e.start_ns), float(e.duration_ns))
    return {"devices": devices, "marker": marker}


def summarize(
    red: dict,
    marker_host_t0: Optional[float] = None,
    window: Optional[Tuple[float, float]] = None,
    host_spans: Sequence[Tuple[str, float, float]] = (),
    top: int = 10,
) -> Optional[dict]:
    """``window``: the measured span (t0, t1) on the host clock (seconds);
    busy time, idle gaps and the window's length are taken inside it only.
    ``marker_host_t0``: the host clock at which the marker annotation was
    opened.  Without a marker in the trace the window is the span of the
    device events and gaps go unnamed."""
    devs = {k: v for k, v in red["devices"].items() if v["ops"]}
    if not devs:
        return None
    off = None  # trace ns = host s * 1e9 + off
    if red["marker"] is not None and marker_host_t0 is not None:
        off = red["marker"][0] - marker_host_t0 * 1e9
    if off is not None and window:
        w0, w1 = window[0] * 1e9 + off, window[1] * 1e9 + off
    else:
        w0 = min(a for v in devs.values() for a, _b, _n in v["ops"])
        w1 = max(b for v in devs.values() for _a, b, _n in v["ops"])
    busy_each, modules, op_time = [], {}, {}
    longest: List[Tuple[float, float, float]] = []
    for v in devs.values():
        merged = union([(max(a, w0), min(b, w1)) for a, b, _n in v["ops"] if b > w0 and a < w1])
        busy_each.append(sum(b - a for a, b in merged) * 1e-9)
        longest.extend((g1 - g0, g0, g1) for g0, g1 in gaps(merged, w0, w1))
        for name, (secs, n) in v["modules"].items():
            m = modules.setdefault(name, [0.0, 0])
            m[0] += secs / len(devs)
            m[1] += n
        for a, b, name in v["ops"]:
            op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9 / len(devs)
    longest.sort(reverse=True)
    named_gaps: Dict[str, float] = {}
    for i, (dur, g0, g1) in enumerate(longest):
        label = "unattributed"
        if i >= NAMED_GAPS:
            label = "many_short_gaps"
        elif off is not None:
            best = 0.0
            for name, s0, s1 in host_spans:
                ov = min(g1, s1 * 1e9 + off) - max(g0, s0 * 1e9 + off)
                if ov > best:
                    best, label = ov, name
            if best < 0.5 * dur:
                label = f"host_other+{label}" if best > 0 else "host_other"
        named_gaps[label] = named_gaps.get(label, 0.0) + dur * 1e-9 / len(devs)
    return {
        "busy_s": sum(busy_each) / len(busy_each),
        "window_s": (w1 - w0) * 1e-9,
        "chips": len(devs),
        "aligned": off is not None,
        "modules": {k: {"seconds": v[0], "events": v[1]} for k, v in modules.items()},
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(named_gaps.items(), key=lambda kv: -kv[1])[:top],
        "longest_gap_s": longest[0][0] * 1e-9 if longest else 0.0,
    }
