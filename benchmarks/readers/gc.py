"""Reader ``gc``: seconds the process stood still in the interpreter's
garbage collector inside the window (every generation; ``gc.callbacks``,
host clock), per 1,000 pods bound in it."""


def read(ctx: dict, params: dict):
    gc_win = ctx.get("gc")
    kpods = ctx.get("pods_in_window", 0) / 1000.0
    if not gc_win or kpods <= 0:
        return None
    return sum(gc_win["seconds"]) / kpods
