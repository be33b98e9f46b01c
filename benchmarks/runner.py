"""One run of one cell: load, warm up, measure, check, reduce.

``run_cell`` is what ``run.py`` calls after it has parsed its arguments.
The rehearsal script and the tests call it too, with ``require_chip=False``
(and sizes cut down), to drive everything but the look for a chip.
"""

from __future__ import annotations

import glob
import os
import time
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional

from benchmarks import cells, correct, trace_reduce, workload
from benchmarks.harness import CompileWatch, GcWatch, PhaseTap, ServedCluster, say

TRACE_DIR = os.path.join(cells.HERE, "_trace")  # listed in .gitignore


class NoChip(RuntimeError):
    pass


def device_doc(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 — a backend without memory stats
            ms = None
        if ms:
            peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def _ledger(sched) -> Dict[str, dict]:
    return {
        r["kernel"]: {"dispatches": r["dispatches"], "compiles": r["compiles"]}
        for r in sched.kernels.table(cost=False)
    }


def _ledger_diff(after: Dict[str, dict], before: Dict[str, dict]) -> Dict[str, dict]:
    out = {}
    for k, row in after.items():
        b = before.get(k, {})
        d = {c: row[c] - b.get(c, 0) for c in row}
        if any(d.values()):
            out[k] = d
    return out


def run_cell(
    cell: dict,
    bench: dict,
    seed: int,
    seconds: float,
    trace: bool,
    t_process_start: float,
    require_chip: bool = True,
    tamper: Optional[Callable[[ServedCluster], None]] = None,
    identity_positions: Optional[List[int]] = None,
    on_identity_position=None,
) -> dict:
    import jax

    import kubernetes_tpu  # noqa: F401 — x64 and the compile cache's directory

    dev = device_doc(jax)
    say(f"device {dev} cache {jax.config.jax_compilation_cache_dir}")
    if require_chip and (dev["platform"] != "tpu" or dev["count"] < cell["chips"]):
        raise NoChip(
            f"cell {cell['name']} asks for {cell['chips']} TPU chip(s); JAX reports {dev}"
        )
    cfg, mix, kind = cell["config"], cell["traffic"], cell["kind"]
    from kubernetes_tpu.tools.paritycheck import device_faults

    with CompileWatch(jax) as watch, GcWatch() as gcw:
        plan = kind.plan(cfg, mix, seed, seconds)
        cluster = ServedCluster(cfg, seed, e_cap_pods=kind.pods_alive(plan))
        try:
            t0 = time.perf_counter()
            cluster.start()
            say(f"cluster up in {time.perf_counter() - t0:.2f}s: {len(cluster.nodes)} nodes, "
                f"{len(cluster.init_specs)} init pods")
            if tamper is not None:
                tamper(cluster)
            t0 = time.perf_counter()
            c0 = watch.compile_s
            kind.warm_up(cluster, plan)
            say(f"warm-up in {time.perf_counter() - t0:.2f}s (compile {watch.compile_s - c0:.2f}s, "
                f"cache hits {watch.cache_hits} misses {watch.cache_misses})")
            offered = kind.offer(cluster, plan)
            sched = cluster.sched
            tap = None
            if trace:
                tap = sched.phases.tracer = PhaseTap()
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                for old in glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*", "*")):
                    os.remove(old)
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            phases0 = sched.phases.snapshot()
            ledger0 = _ledger(sched)
            compiles0, gc0 = watch.reading(), gcw.reading()
            setup_s = time.perf_counter() - t_process_start
            marker_t0 = time.perf_counter()
            marker = jax.profiler.TraceAnnotation(trace_reduce.MARKER) if trace else nullcontext()
            with marker:
                win = kind.window(cluster, offered, seconds)
            compiles1, gc1 = watch.reading(), gcw.reading()
            phases = sched.phases.diff(sched.phases.snapshot(), phases0)
            ledger1 = _ledger(sched)
            ledger = _ledger_diff(ledger1, ledger0)
            if trace:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                sched.phases.tracer = None
                say(f"trace stopped and written in {time.perf_counter() - t_stop:.2f}s")
            cluster.hold_loop()
            store = cluster.read_back()
            acked = cluster.snapshot_acked()
            order, popped = cluster.snapshot_order()
            faults = device_faults(sched)
            logged = cluster.log_tap.faults()
            dispatches = {k: row["dispatches"] for k, row in ledger1.items()}
            dev = device_doc(jax)
        finally:
            cluster.stop()

    # ---- correct, outside the window and outside set-up -----------------------
    t_check = time.perf_counter()
    all_specs = cluster.init_specs + plan["measure"]  # the warm-up is deleted in set-up
    checks, good = correct.check_guarantee(
        acked, store, cluster.double_binds, [workload.uid_of(s) for s in all_specs]
    )
    red = kind.reduce(offered, win, acked, good)
    compiles_in_window = compiles1 - compiles0
    compiles_in_window += sum(r.get("compiles", 0) for r in ledger.values())
    checks += correct.check_device(
        dev, faults, logged, dispatches, cfg["expect_kernels"], compiles_in_window, require_chip,
    )
    checks += correct.check_feasibility(cluster.nodes, all_specs, store)
    by_uid = {workload.uid_of(s): s for s in plan["measure"]}
    last = {}  # uid -> the decision that stands (a retried pod's last one)
    for u, n in order:
        last[u] = n
    # the window's serial order: queue-pop order, a retried pod at its last pop
    last_pop = {u: i for i, u in enumerate(popped)}
    window_order = [
        (by_uid[u], last.get(u)) for i, u in enumerate(popped)
        if u in by_uid and last_pop[u] == i
    ]
    stale = [(u, n, store.get(u)) for u, n in last.items() if (n or "") != (store.get(u) or "")]
    checks.append(correct.Check(
        "guarantee", "decisions_not_read_back", len(stale), 0, not stale,
        f"first {stale[:3]}" if stale else f"{len(last)} decisions equal the store"))
    bound_before = list(zip(cluster.init_specs, cluster.init_nodes))
    checks += correct.check_identity(
        cluster.nodes, bound_before, window_order, cfg["identity_sample"], seed,
        positions=identity_positions, on_position=on_identity_position,
    )
    for c in checks:
        say(c.line())
    say(f"correct decided in {time.perf_counter() - t_check:.2f}s")
    ok = all(c.ok for c in checks)

    # ---- metrics ----------------------------------------------------------------
    e2e = dict(red["metrics"])
    e2e["setup_s"] = setup_s
    say(f"window {red['window_s']:.3f}s, {red['pods_in_window']} of {red['attempted']} bound, "
        f"compiles in window {compiles_in_window}, phases "
        f"{ {k_: round(v, 3) for k_, v in sorted(phases.items())} }")
    say(f"ledger in window { {k_: v['dispatches'] for k_, v in ledger.items()} }")
    gc_win = {k_: [a - b for a, b in zip(gc1[k_], gc0[k_])] for k_ in gc1}
    say(f"garbage collector in window: collections {gc_win['collections']} by generation, "
        f"seconds {[round(x, 3) for x in gc_win['seconds']]}")
    result = {
        "correct": ok,
        "attempted": red["attempted"],
        "failed": red["failed"],
        "device": dev,
    }
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    if not trace:
        names = cells.end_to_end_names(cell["name"], bench)
        result["metrics"] = {
            n: {"value": e2e[n], "unit": units[n]} for n in names if n in e2e
        }
        return result
    summary = None
    paths = sorted(glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
    if paths:
        t_red = time.perf_counter()
        planes = list(trace_reduce.load(paths[-1]).planes)
        for pl in planes:
            lines = {ln.name: sum(1 for _ in ln.events) for ln in pl.lines}
            if trace_reduce.is_device_plane(pl.name):
                say(f"trace plane {pl.name}: {lines}")
        summary = trace_reduce.summarize(
            trace_reduce.reduce_planes(planes),
            marker_host_t0=marker_t0,
            window=red["window"],
            host_spans=tap.spans,
        )
        say(f"trace {os.path.getsize(paths[-1])} bytes reduced in {time.perf_counter() - t_red:.2f}s; "
            f"host clock: marker {marker_t0!r} window {red['window']!r}")
    ctx = {
        "phases": phases,
        "ledger": ledger,
        "gc": gc_win,
        "pods_in_window": red["pods_in_window"],
        "trace": summary,
        "device": dev,
        "n_nodes": len(cluster.nodes),
        "n_lanes": len(cfg["nodes"]["capacity"]),
        "nodes_touched": len({store[u] for u in by_uid if store.get(u)}),
        "offered": offered,
        "window": win,
    }
    metrics = {}
    for spec in cells.layer_metrics(cell["name"], bench):
        v = spec["read"](ctx, spec.get("params", {}))
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": units[spec["name"]]}
    result["metrics"] = metrics
    if summary is not None:
        say(f"trace: busy {summary['busy_s']:.4f}s of {summary['window_s']:.4f}s on "
            f"{summary['chips']} chip(s), clocks aligned {summary['aligned']}, modules "
            f"{ {k_: (round(v['seconds'], 4), v['events']) for k_, v in summary['modules'].items()} }")
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary["device_ops"]],
            "idle_gaps": [[n, s] for n, s in summary["idle_gaps"]],
        }
    return result
