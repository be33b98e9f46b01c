"""Reader ``scope_under``: device seconds of every op traced UNDER a stage
name, whatever stage lies further in.

``scope`` counts an op under its innermost ``ktpu/<module>/<stage>``.  A
stage that only wraps calls which carry stages of their own is then never an
op's innermost one: ``ktpu/wave/speculation`` wraps the per-pod filter, score
and select, and ``scope`` reads 0 for it on the chip (PERF.md, Findings PR 30)
while its ops sit under ``ktpu/gang/select``.  This reader hands ``scope``'s
own table builder the same ops with each ``tf_op`` cut down to the one
question "is ``params.stage`` anywhere in it", so the same events, window,
self times and placing of unnamed helper ops apply.  Its numbers OVERLAP the
innermost stages' (speculation's seconds are also in ``select``): never add
them to those.

``params``: ``modules`` as for ``scope``; ``stage``; ``what``:
``stage_ms_per_kpod`` only.  ``None`` where ``scope`` reads ``None``.
"""

from __future__ import annotations

from benchmarks import runner
from benchmarks.readers import scope, xspan


def under(planes, tf_ops: dict, stage: str, modules, window):
    """Seconds of the ops whose ``tf_op`` holds ``stage``; None where no op
    of ``modules`` ran."""
    marked = {name: (stage if stage in tf_op else "") for name, tf_op in tf_ops.items()}
    table = scope.by_stage(planes, marked, modules, window)
    return None if table is None else table.get(stage, 0.0)


def read(ctx: dict, params: dict):
    if params["what"] != "stage_ms_per_kpod":
        raise ValueError(f"scope_under reader: unknown what={params['what']!r}")
    kpods = ctx.get("pods_in_window", 0) / 1000.0
    if kpods <= 0 or not scope._program_stages():
        return None
    col = xspan.load(ctx)
    if col is None or col["window"] is None:
        return None
    if "_scope_under:tf_ops" not in ctx:  # each metric loads this file anew
        with open(col["path"], "rb") as f:
            ctx["_scope_under:tf_ops"] = scope.tf_ops(f.read())
    secs = under(col["planes"], ctx["_scope_under:tf_ops"], params["stage"],
                 params["modules"], col["window"])
    if secs is None:
        return None
    runner.say(f"scope_under: {secs:.6f} device s (self time) under {params['stage']}")
    return 1000.0 * secs / kpods
