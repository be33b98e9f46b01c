"""Reader ``trace``: numbers of the reduced profiler trace
(``trace_reduce.summarize``).  ``what``:

* ``module_ms_per_kpod`` — summed device milliseconds of the XLA modules
  whose names match one of ``modules`` (regular expressions), per 1,000
  pods bound in the traced window;
* ``idle_share`` — 100 x (1 - busy / window);
* ``roofline_share`` — 100 x floor seconds / module seconds, the floor
  from ``roofline.floor_seconds`` on the window's own shapes.
"""

import re

from benchmarks import roofline


def _module_seconds(trace: dict, patterns) -> tuple:
    secs, events = 0.0, 0
    for name, m in trace["modules"].items():
        if any(re.search(p, name) for p in patterns):
            secs += m["seconds"]
            events += m["events"]
    return secs, events


def read(ctx: dict, params: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    what = params["what"]
    if what == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    secs, events = _module_seconds(trace, params["modules"])
    if secs <= 0:
        return None
    if what == "module_ms_per_kpod":
        kpods = ctx.get("pods_in_window", 0) / 1000.0
        return 1000.0 * secs / kpods if kpods > 0 else None
    if what == "roofline_share":
        floor = roofline.floor_seconds(
            ctx["device"]["kind"],
            n_nodes=ctx["n_nodes"],
            n_lanes=ctx["n_lanes"],
            dispatches=events,
            pods=ctx["pods_in_window"],
            nodes_touched=ctx["nodes_touched"],
        )
        return 100.0 * floor / secs
    raise ValueError(f"trace reader: unknown what={what!r}")
