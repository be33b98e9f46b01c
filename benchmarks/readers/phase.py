"""Reader ``phase``: seconds the program's ``PhaseAccumulator`` booked to
the named phases inside the window, per 1,000 pods bound in it.  Host
seconds: ``device`` there is the host clock around a dispatch, not device
time."""


def read(ctx: dict, params: dict):
    phases = ctx.get("phases")
    kpods = ctx.get("pods_in_window", 0) / 1000.0
    if not phases or kpods <= 0:
        return None
    return sum(phases.get(p, 0.0) for p in params["phases"]) / kpods
