"""Reader ``scope``: device seconds of the scheduling modules by stage name.

The scheduling roots wrap their stages in ``jax.named_scope("ktpu/<module>/
<stage>")`` (``kubernetes_tpu/ops/common.py`` ``STAGES``), so each compiled
instruction's ``op_name`` carries the stages it was traced under.  On the
chip the profiler writes that ``op_name`` into the trace as the stat
``tf_op`` of the op's EVENT METADATA (not of the event, and
``jax.profiler.ProfileData`` hands out only an event's own stats), so this
reader takes the events from ``ProfileData`` and the ``event name ->
tf_op`` table from the same ``*.xplane.pb`` with a few lines of protobuf
wire format (``tf_ops``): one file, one clock, the profiler's.

Counted: the ops (line ``XLA Ops``) that start inside an ``XLA Modules``
event whose name matches one of ``params.modules`` and inside the window
(the ``bench_window`` marker, as ``xspan`` takes it).  An op that encloses
others (``while``, ``conditional``, ``call``) is counted for its SELF time
only: its duration less the ops it directly encloses.  An op's stage is
the innermost (last) ``ktpu/<module>/<stage>`` of its ``tf_op``.  An
instruction with NO ``op_name`` at all (helper fusions the TPU compiler
makes itself: in the kept trace of ``spread-5k`` each runs directly before
the gather it prepares, 13 % of the device's seconds) is counted with the
next op of the same module execution that has one; the printed table says
how many seconds were placed that way.

``params.what``:

* ``scoped_share``      — 100 x seconds under a stage name / all seconds;
* ``stage_ms_per_kpod`` — milliseconds under ``params.stage`` per 1,000
                          pods bound in the window.

``None`` (metric left out) where the trace has no device plane, no marker,
no op of those modules, or the program has no stage names at all (a
program from before them).  Where the program has them but a run's ops
carry none — an executable loaded from a compile cache written before the
names existed: metadata is not part of the cache's key — the share is 0.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import runner, trace_reduce

STAGE = re.compile(r"ktpu/[a-z_]+/[a-z_]+")
UNSCOPED = "(no stage)"


# ---- the xplane file's event metadata, by wire format ------------------------
# XSpace{planes=1}; XPlane{name=2, event_metadata=4 (map<int64, XEventMetadata>),
# stat_metadata=5 (map<int64, XStatMetadata>)}; XEventMetadata{name=2, stats=5};
# XStat{metadata_id=1, str_value=5, ref_value=7}; XStatMetadata{id=1, name=2}.


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterable[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview-free ``bytes`` slice."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val = buf[i:i + 8]
            i += 8
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wt}")
        yield num, wt, val


def _map_value(entry: bytes) -> bytes:
    for num, wt, val in _fields(entry):
        if num == 2 and wt == 2:
            return val
    return b""


def _text(raw: bytes) -> str:
    return raw.decode("utf-8", "replace")


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[Optional[str], Optional[str]]:
    """(stat name, string value) of one XStat; a ``ref_value`` names its
    string through the plane's stat metadata."""
    sid, val = 0, None
    for num, wt, v in _fields(buf):
        if num == 1 and wt == 0:
            sid = v
        elif num == 5 and wt == 2:
            val = _text(v)
        elif num == 7 and wt == 0:
            val = stat_names.get(v, "")
    return stat_names.get(sid), val


def tf_ops(xspace: bytes, stat: str = "tf_op") -> Dict[str, str]:
    """``event metadata name -> tf_op`` over the device planes of a
    serialized XSpace."""
    out: Dict[str, str] = {}
    for num, wt, plane in _fields(xspace):
        if num != 1 or wt != 2:
            continue
        name, metas, stat_names = "", [], {}
        for pn, pwt, val in _fields(plane):
            if pn == 2 and pwt == 2:
                name = _text(val)
            elif pn == 4 and pwt == 2:
                metas.append(_map_value(val))
            elif pn == 5 and pwt == 2:
                meta = {n: v for n, _wt, v in _fields(_map_value(val))}
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not trace_reduce.is_device_plane(name):
            continue
        for meta in metas:
            ev_name, found = "", None
            for mn, mwt, mv in _fields(meta):
                if mn == 2 and mwt == 2:
                    ev_name = _text(mv)
                elif mn == 5 and mwt == 2:
                    sname, sval = _stat(mv, stat_names)
                    if sname == stat and sval is not None:
                        found = sval
            if found is not None:
                out[ev_name] = found
    return out


# ---- from events to seconds by stage ------------------------------------------


def stage_of(tf_op: Optional[str]) -> Optional[str]:
    found = STAGE.findall(tf_op or "")
    return found[-1] if found else None


def self_times(ops: Sequence[Tuple[float, float, str]]) -> List[Tuple[float, str]]:
    """(self nanoseconds, name) of each op: its duration less the ops it
    directly encloses.  ``ops``: (start, end, name) of one device's line."""
    order = sorted(range(len(ops)), key=lambda k: (ops[k][0], -ops[k][1]))
    self_ns = [b - a for a, b, _n in ops]
    stack: List[int] = []
    for k in order:
        a, b, _n = ops[k]
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][1]:
            self_ns[stack[-1]] -= b - a
        stack.append(k)
    return [(max(self_ns[k], 0.0), ops[k][2]) for k in range(len(ops))]


def by_stage(planes, op_names: Dict[str, str], modules: Sequence[str],
             window: Optional[Tuple[float, float]]) -> Optional[Dict[str, float]]:
    """``{stage or UNSCOPED: seconds}`` (averaged over the device planes that
    ran such an op); under ``"_ops"`` the same per (stage, op), under
    ``"_placed"`` the seconds of ops with no ``op_name`` of their own that
    were placed with their neighbour.  None where no op of those modules
    ran.  ``planes`` as ``ProfileData`` gives them (the tests hand in
    stand-ins)."""
    each = []
    per_op: Dict[Tuple[str, str], float] = {}
    placed = 0.0
    for plane in planes:
        if not trace_reduce.is_device_plane(plane.name):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                for e in line.events:
                    if any(re.search(p, trace_reduce.module_name(e.name)) for p in modules):
                        a = float(e.start_ns)
                        mods.append((a, a + float(e.duration_ns)))
            elif line.name == trace_reduce.OPS_LINE:
                for e in line.events:
                    a = float(e.start_ns)
                    ops.append((a, a + float(e.duration_ns), e.name))
        totals: Dict[str, float] = {}

        def book(stage: Optional[str], name: str, secs: float) -> None:
            stage = stage or UNSCOPED
            totals[stage] = totals.get(stage, 0.0) + secs
            key = (stage, trace_reduce.op_name(name))
            per_op[key] = per_op.get(key, 0.0) + secs

        timed = sorted((a, self_ns * 1e-9, name) for (a, _b, _n), (self_ns, name) in zip(ops, self_times(ops)))
        starts = [a for a, _s, _n in timed]
        for m0, m1 in mods:  # one module execution at a time
            if window is not None:
                m0, m1 = max(m0, window[0]), min(m1, window[1])
            stage, waiting = None, []  # the last named op's stage; unnamed ops since
            for _a, secs, name in timed[bisect.bisect_left(starts, m0):bisect.bisect_left(starts, m1)]:
                tf_op = op_names.get(name)
                if tf_op is None:
                    # no op_name at all (the compiler's own helper fusions;
                    # each directly precedes the op it prepares): goes with
                    # the next op of this module execution that has one
                    waiting.append((name, secs))
                    continue
                stage = stage_of(tf_op)
                book(stage, name, secs)
                for n, w_secs in waiting:
                    book(stage, n, w_secs)
                    placed += w_secs
                waiting = []
            for n, w_secs in waiting:  # the execution ended on unnamed ops
                book(stage, n, w_secs)
        if totals:
            each.append(totals)
    if not each:
        return None
    out = {k: sum(t.get(k, 0.0) for t in each) / len(each) for t in each for k in t}
    out["_ops"] = {k: v / len(each) for k, v in per_op.items()}
    out["_placed"] = placed / len(each)
    return out


def _stages(table: dict) -> Dict[str, float]:
    return {k: v for k, v in table.items() if not k.startswith("_")}


def _program_stages() -> Tuple[str, ...]:
    try:
        from kubernetes_tpu.ops.common import STAGES
    except ImportError:  # a program from before the stage names
        return ()
    return tuple(STAGES)


def _load(ctx: dict, modules: Sequence[str]) -> Optional[Dict[str, float]]:
    """The run's table, built once per run and module list (kept in ``ctx``:
    each metric loads this file anew), printed once."""
    key = "_scope:" + "|".join(modules)
    if key in ctx:
        return ctx[key]
    ctx[key] = None
    if not _program_stages():
        return None
    from benchmarks.readers import xspan  # the run's trace and its window

    col = xspan.load(ctx)
    if col is None or col["window"] is None:
        return None
    with open(col["path"], "rb") as f:
        names = tf_ops(f.read())
    table = ctx[key] = by_stage(col["planes"], names, modules, col["window"])
    if table is None:
        return None
    ops = table["_ops"]
    stages = _stages(table)
    total = sum(stages.values())
    runner.say(f"scope: {total:.6f} device s (self time) in modules {list(modules)}, "
               f"{len(names)} instructions with an op_name, {table['_placed']:.6f}s of ops without one "
               f"placed with the next op; stage, seconds, share, largest ops:")
    for stage, secs in sorted(stages.items(), key=lambda kv: -kv[1]):
        top = sorted(((v, o) for (s, o), v in ops.items() if s == stage), reverse=True)[:4]
        runner.say(f"scope   {stage:<32} {secs:>10.6f} {100 * secs / total:>6.2f}%  "
                   + ", ".join(f"{o} {v:.4f}" for v, o in top))
    return table


def read(ctx: dict, params: dict):
    table = _load(ctx, params["modules"])
    if table is None:
        return None
    stages = _stages(table)
    total = sum(stages.values())
    what = params["what"]
    if what == "scoped_share":
        return 100.0 * (total - stages.get(UNSCOPED, 0.0)) / total if total > 0 else None
    if what == "stage_ms_per_kpod":
        kpods = ctx.get("pods_in_window", 0) / 1000.0
        return 1000.0 * stages.get(params["stage"], 0.0) / kpods if kpods > 0 else None
    raise ValueError(f"scope reader: unknown what={what!r}")
