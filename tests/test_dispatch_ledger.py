"""Device telemetry ledger (observability/kernels.py, ISSUE 13).

Covers the acceptance surface:
  * every registered jit root appears in the ledger (roster coverage —
    a new kernel cannot land unobserved);
  * a drain's dispatches land per-kernel on /metrics and /debug/kernels
    (dispatch counts, execute histogram, compile split);
  * per-kernel d2h attribution sums EXACTLY to
    scheduler_tpu_d2h_bytes_total (untagged fetches under _untagged);
  * the kernelLedger kill switch is a no-op identity: same decisions,
    nothing recorded, and the wrapper's disabled path stays one global
    read + branch;
  * cost-analysis memoization: repeat shapes hit the memo, never a
    second lowering;
  * the regression sentinel: a synthetically slowed kernel breaches
    after the sustained threshold and the SLO tier's black-box
    freeze→dump fires with the kernel NAMED in the breach record;
  * dispatch_submit-track spans ride the PR-4 tracer export;
  * /debug/kernels + the /debug/ JSON index round-trip over the real
    HTTP server, and the plain-text help block is generated from the
    same table (no drift possible);
  * planner dispatches are tracer-visible (dispatch.plan/harvest.plan)
    and leave a `plan` flight-recorder breadcrumb.
"""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Container,
    LabelSelector,
    Node,
    Pod,
    TopologySpreadConstraint,
)
from kubernetes_tpu.framework.config import SchedulerConfiguration
from kubernetes_tpu.observability import kernels as kernels_mod
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.fake_cluster import FakeCluster


def _nodes(n=4, cpu="8"):
    return [
        Node(
            name=f"n{i}",
            labels={
                "kubernetes.io/hostname": f"n{i}",
                "topology.kubernetes.io/zone": f"z{i % 2}",
            },
            capacity=Resource.from_map({"cpu": cpu, "memory": "32Gi"}),
        )
        for i in range(n)
    ]


def _pod(name, cpu="100m", **kw):
    return Pod(
        name=name,
        containers=[Container(requests={"cpu": cpu, "memory": "64Mi"})],
        **kw,
    )


def _spread_pod(name):
    return Pod(
        name=name,
        labels={"app": "web"},
        containers=[Container(requests={"cpu": "100m", "memory": "64Mi"})],
        topology_spread_constraints=(
            TopologySpreadConstraint(
                max_skew=1,
                topology_key="topology.kubernetes.io/zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "web"}),
            ),
        ),
    )


def _drained_sched(configuration=None, n_nodes=6, n_pods=40, spread=8):
    api = FakeCluster()
    sched = Scheduler(configuration=configuration)
    api.connect(sched)
    for n in _nodes(n_nodes):
        api.create_node(n)
    for i in range(spread):
        api.create_pod(_spread_pod(f"s{i}"))
    for i in range(n_pods):
        api.create_pod(_pod(f"p{i}"))
    outs = sched.schedule_pending()
    return sched, outs


class _FakeRoot:
    """Stands in for a jit root: a callable with ``_cache_size`` whose
    delay the test turns (the 'synthetically slowed kernel')."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.calls = 0

    def _cache_size(self):
        return 1  # never grows: every dispatch counts as warm execute

    def __call__(self, *a, **kw):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        return None


# ---------------------------------------------------------------------------
# roster coverage + dispatch accounting
# ---------------------------------------------------------------------------


def test_every_sanitizer_root_appears_in_ledger():
    """The CI coverage gate: the sanitizer's jit-root registry must be a
    subset of the ledger's roster — a new kernel cannot land without
    per-kernel accounting."""
    from kubernetes_tpu.analysis import sanitizer

    sched, _ = _drained_sched(n_pods=4, spread=0)
    assert sched.kernels.enabled
    names = {r["kernel"] for r in sched.kernels.table(cost=False)}
    discovered = set(sanitizer._discover_jit_roots())
    assert discovered, "no jit roots discovered — the seam moved?"
    missing = discovered - names
    assert not missing, f"jit roots unobserved by the ledger: {missing}"
    # runtime-registered roots join the roster through the listener seam
    fake = _FakeRoot()
    sanitizer.register_jit_root("runtime.late_root", fake)
    assert "runtime.late_root" in kernels_mod.roster()


def test_install_after_runtime_roots_does_not_deadlock():
    """install() subscribes to the sanitizer's jit-root listener, whose
    replay of already-registered roots re-enters the install lock — the
    subscription must happen OUTSIDE it (regression: a process that ran
    mark_jit_warm()/register_jit_root() before its first ledger-enabled
    Scheduler hung forever in Scheduler.__init__)."""
    import threading

    from kubernetes_tpu.analysis import sanitizer

    sanitizer.register_jit_root("runtime.pre_install_root", _FakeRoot())
    done = threading.Event()

    def run():
        kernels_mod.install()
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert done.wait(30), "kernels.install() deadlocked"
    assert "runtime.pre_install_root" in kernels_mod.roster()


def test_drain_reports_per_kernel_dispatches_and_metrics():
    sched, outs = _drained_sched()
    assert all(o.node is not None for o in outs)
    rows = {
        r["kernel"]: r
        for r in sched.kernels.table(cost=False)
        if r["dispatches"]
    }
    assert rows, "no dispatches recorded"
    # the spread pods force the wave dispatch (the plain pods may commit
    # on the host greedy with zero device round trips — that is the
    # point of the fast path, and the ledger must reflect it honestly)
    assert "wave.wave_run" in rows
    for name, r in rows.items():
        assert (
            sched.prom.kernel_dispatches.value(kernel=name) == r["dispatches"]
        )
        assert r["compiles"] + sched.prom.kernel_execute.count(
            kernel=name
        ) == r["dispatches"], name
        assert r["shape_buckets"] >= 1
    # compile split: first-ever dispatch of each root compiles
    assert all(r["compiles"] >= 1 for r in rows.values())
    exposition = sched.expose_metrics()
    assert (
        'scheduler_tpu_kernel_dispatches_total{kernel="wave.wave_run"}'
        in exposition
    )
    assert "scheduler_tpu_kernel_execute_seconds" in exposition


def test_bucket_key_carries_device_count_and_mesh_shape():
    """ISSUE 14: single-chip and mesh-partitioned dispatches of the SAME
    shapes land in different shape buckets (device count + mesh shape
    ride the key), and /debug/kernels surfaces the placement — the
    regression sentinel's per-bucket series can't smear across layouts."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the 8-virtual-device backend")
    on_sched, _ = _drained_sched(
        SchedulerConfiguration(mesh_dispatch=True)
    )
    on = {
        r["kernel"]: r
        for r in on_sched.kernels.table(cost=False)
        if r["dispatches"]
    }
    ndev = len(jax.devices())
    wave = on["wave.wave_run"]
    assert max(wave["devices"]) == ndev, wave
    assert wave["multi_device_dispatches"] >= 1
    assert wave["mesh_shapes"], wave  # e.g. ['8x1']
    assert on_sched.kernels.stats()["multi_device_dispatches"] >= 1
    off_sched, _ = _drained_sched(
        SchedulerConfiguration(mesh_dispatch=False)
    )
    off = {
        r["kernel"]: r
        for r in off_sched.kernels.table(cost=False)
        if r["dispatches"]
    }
    assert off["wave.wave_run"]["devices"] == [1]
    assert off["wave.wave_run"]["multi_device_dispatches"] == 0
    assert off["wave.wave_run"]["mesh_shapes"] == []
    # same drain, same shapes — different buckets by placement alone
    on_keys = set(on_sched.kernels._kstats["wave.wave_run"].buckets)
    off_keys = set(off_sched.kernels._kstats["wave.wave_run"].buckets)
    assert on_keys.isdisjoint(off_keys)


def test_d2h_attribution_sums_to_total():
    sched, _ = _drained_sched()
    # force an untagged fetch too (seeded tiebreak path is untagged, but
    # don't rely on it): any direct _d2h without a kernel context
    import jax.numpy as jnp

    sched._d2h(jnp.zeros((16,), jnp.int32))
    rows = sched.kernels.table(cost=False)
    total = sched.prom.d2h_bytes.value()
    assert total > 0
    assert sum(r["d2h_bytes"] for r in rows) == total
    per_metric = sum(
        sched.prom.kernel_d2h_bytes.value(kernel=r["kernel"]) for r in rows
    )
    assert per_metric == total
    untagged = next(r for r in rows if r["kernel"] == "_untagged")
    assert untagged["d2h_bytes"] >= 16 * 4


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------


def test_kill_switch_identity_and_no_recording():
    on_sched, on_outs = _drained_sched()
    placements_on = sorted(
        (o.pod.name, o.node) for o in on_outs if o.node is not None
    )
    off_sched, off_outs = _drained_sched(
        configuration=SchedulerConfiguration(kernel_ledger=False)
    )
    placements_off = sorted(
        (o.pod.name, o.node) for o in off_outs if o.node is not None
    )
    # the ledger only observes: decisions are bit-identical
    assert placements_on == placements_off
    # and the off scheduler recorded NOTHING
    assert not off_sched.kernels.enabled
    assert all(
        r["dispatches"] == 0 and r["d2h_bytes"] == 0
        for r in off_sched.kernels.table(cost=False)
    )
    assert "scheduler_tpu_kernel_dispatches_total{" not in (
        off_sched.expose_metrics()
    )


def test_disabled_wrapper_passes_through():
    kernels_mod.deactivate()
    fake = _FakeRoot()
    root = kernels_mod._LedgerRoot("fake.root", fake)
    assert root() is None and fake.calls == 1
    assert root._cache_size() == 1  # attribute proxying
    led = kernels_mod.DispatchLedger()
    kernels_mod.activate(led)
    try:
        root()
        assert led.stats()["dispatches"] == 1
        led.enabled = False
        root()
        assert led.stats()["dispatches"] == 1  # kill switch: passthrough
    finally:
        kernels_mod.deactivate(led)


def test_in_trace_calls_are_not_dispatches():
    """A root tracing through another root (jit-of-jit) must not record
    phantom dispatches — only host-level calls are dispatches."""
    import jax
    import jax.numpy as jnp

    led = kernels_mod.DispatchLedger()
    inner = jax.jit(lambda x: x * 2)
    calls = []

    def outer_fn(x):
        calls.append(1)
        return led.dispatch("test.inner", inner, (x,), {})

    outer = jax.jit(outer_fn)
    kernels_mod.activate(led)
    try:
        y = led.dispatch("test.outer", outer, (jnp.ones((4,)),), {})
        assert float(y.sum()) == 8.0
        st = led.stats()
        seen = {
            r["kernel"]: r["dispatches"]
            for r in led.table(cost=False)
            if r["dispatches"]
        }
        assert seen == {"test.outer": 1}, seen
        assert st["dispatches"] == 1
    finally:
        kernels_mod.deactivate(led)


# ---------------------------------------------------------------------------
# cost analysis memo
# ---------------------------------------------------------------------------


def test_cost_analysis_memo_hit_on_repeat_shapes():
    import jax
    import jax.numpy as jnp

    led = kernels_mod.DispatchLedger()
    fn = jax.jit(lambda x: x @ x.T)
    name = "test.matmul"
    kernels_mod._wrapped[name] = (None, None, fn)
    try:
        for _ in range(3):  # repeat shape: ONE bucket
            led.dispatch(name, fn, (jnp.ones((8, 4)),), {})
        rows = {r["kernel"]: r for r in led.table(cost=True)}
        r = rows[name]
        assert r["dispatches"] == 3 and r["shape_buckets"] == 1
        assert r["est_flops"] > 0 and r["est_bytes_accessed"] > 0
        st = led.stats()
        assert st["cost_memo_misses"] == 1
        led.table(cost=True)  # repeat request: memo hit, no new lowering
        st2 = led.stats()
        assert st2["cost_memo_misses"] == 1
        assert st2["cost_memo_hits"] >= 1
    finally:
        del kernels_mod._wrapped[name]


# ---------------------------------------------------------------------------
# regression sentinel → blackbox dump
# ---------------------------------------------------------------------------


def test_sentinel_breach_freezes_and_dumps_with_kernel_named(tmp_path):
    from kubernetes_tpu.observability.slo import SLOConfig

    sched = Scheduler()
    sched.install_slo(
        SLOConfig(dump_dir=str(tmp_path), breach_cooldown_s=0.0)
    )
    led = sched.kernels
    led.sentinel_min_samples = 4
    led.sentinel_sustain = 3
    led.sentinel_factor = 2.0
    led.sentinel_floor_s = 0.0001
    fake = _FakeRoot(delay_s=0.001)
    for _ in range(6):
        led.dispatch("fake.slow_kernel", fake, (), {})
    assert not led.stats()["regressions"]  # baseline established, calm
    fake.delay_s = 0.05  # the synthetic slowdown
    for _ in range(3):
        led.dispatch("fake.slow_kernel", fake, (), {})
    regs = led.stats()["regressions"]
    assert regs and regs[-1]["kernel"] == "fake.slow_kernel"
    assert (
        sched.prom.kernel_regressions.value(kernel="fake.slow_kernel") == 1
    )
    # the breach rode the PR-7 machinery: record filed, artifact dumped,
    # ring re-armed for the next incident
    snap = sched.slo.snapshot()
    lb = snap["last_breach"]
    assert lb["objective"] == "kernel_regression"
    assert lb["kernel"] == "fake.slow_kernel"
    assert lb["trace"] is not None
    dumped = json.load(open(lb["trace"]))
    assert "traceEvents" in dumped
    assert sched.tracer.enabled
    assert sched.tracer.stats()["mode"] == "blackbox"
    # a permanently slowed kernel re-breaches only after re-sustaining
    for _ in range(3):
        led.dispatch("fake.slow_kernel", fake, (), {})
    assert (
        sched.prom.kernel_regressions.value(kernel="fake.slow_kernel") == 2
    )


def test_sentinel_baseline_ignores_outliers_and_compiles():
    led = kernels_mod.DispatchLedger(
        sentinel_min_samples=4, sentinel_sustain=3, sentinel_factor=2.0,
        sentinel_floor_s=0.0001,
    )

    class GrowingCache(_FakeRoot):
        def __init__(self):
            super().__init__()
            self.size = 0

        def _cache_size(self):
            return self.size

        def __call__(self, *a, **kw):
            self.size += 1  # every call traces a fresh shape
            return super().__call__(*a, **kw)

    fake = GrowingCache()
    # a compile storm (cache growth) never feeds the sentinel
    fake.delay_s = 0.05
    for _ in range(10):
        led.dispatch("fake.compiling", fake, (), {})
    rows = {r["kernel"]: r for r in led.table(cost=False)}
    assert rows["fake.compiling"]["compiles"] == 10
    assert rows["fake.compiling"]["regressions"] == 0
    # one isolated spike (streak < sustain) is not a breach, and it does
    # NOT drag the baseline up
    calm = _FakeRoot(delay_s=0.001)
    for _ in range(6):
        led.dispatch("fake.spiky", calm, (), {})
    base = led.table(cost=False)
    base_s = next(
        r for r in base if r["kernel"] == "fake.spiky"
    )["baseline_s"]
    calm.delay_s = 0.05
    led.dispatch("fake.spiky", calm, (), {})
    calm.delay_s = 0.001
    for _ in range(3):
        led.dispatch("fake.spiky", calm, (), {})
    after = next(
        r
        for r in led.table(cost=False)
        if r["kernel"] == "fake.spiky"
    )
    assert after["regressions"] == 0
    assert after["baseline_s"] < base_s * 2


# ---------------------------------------------------------------------------
# tracer dispatch_submit track (the host's clock around the dispatch call:
# submit time on an asynchronous backend, never device time)
# ---------------------------------------------------------------------------


def test_dispatch_submit_track_spans_ride_the_tracer():
    sched = Scheduler()
    led = sched.kernels
    sched.tracer.start()
    fake = _FakeRoot()
    led.dispatch("fake.traced", fake, (), {})
    sched.tracer.stop()
    trace = sched.tracer.export()
    spans = [
        e for e in trace["traceEvents"] if e.get("name") == "fake.traced"
    ]
    assert spans and spans[0]["ph"] == "X"
    assert spans[0]["cat"] == "dispatch_submit"
    track_meta = [
        e
        for e in trace["traceEvents"]
        if e.get("ph") == "M" and e["args"].get("name") == "dispatch_submit"
    ]
    assert not [
        e
        for e in trace["traceEvents"]
        if e.get("ph") == "M" and e["args"].get("name") == "device"
    ]
    assert track_meta and spans[0]["tid"] == track_meta[0]["tid"]
    # the synthetic track never collides with an OS thread ident
    assert spans[0]["tid"] >= (1 << 40)


# ---------------------------------------------------------------------------
# HTTP: /debug/kernels + the /debug/ index
# ---------------------------------------------------------------------------


def _get(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as r:
            return r.status, r.headers["Content-Type"], r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.headers["Content-Type"], e.read().decode()


def test_debug_kernels_and_index_http_round_trip():
    from kubernetes_tpu.server import (
        DEBUG_ENDPOINTS,
        SchedulerServer,
        debug_help_text,
    )

    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    for n in _nodes(3):
        api.create_node(n)
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    server.start()
    try:
        port = server.port
        api.create_pod(_pod("served"))
        deadline = time.time() + 60  # a first compile beside five busy workers can take more than 10 s
        while time.time() < deadline:
            if sched.prom.kernel_dispatches.value(
                kernel="fastpath.static_eval"
            ):
                break
            time.sleep(0.05)
        # the per-kernel table (cost=0 keeps the request fast)
        code, ctype, body = _get(port, "/debug/kernels?cost=0")
        assert code == 200 and ctype.startswith("application/json")
        snap = json.loads(body)
        assert snap["enabled"] and isinstance(snap["kernels"], list)
        row = next(
            r
            for r in snap["kernels"]
            if r["kernel"] == "fastpath.static_eval"
        )
        assert row["dispatches"] >= 1 and "execute_p99_s" in row
        assert "memory" in snap and "regressions" in snap
        # the JSON index: every catalogued endpoint, nothing invented
        code, ctype, body = _get(port, "/debug/")
        assert code == 200 and ctype.startswith("application/json")
        index = json.loads(body)
        assert [e["path"] for e in index["endpoints"]] == [
            p for p, _, _ in DEBUG_ENDPOINTS
        ]
        assert all(e["description"] for e in index["endpoints"])
        # the plain-text help is GENERATED from the same table
        code, ctype, body = _get(port, "/debug/?format=text")
        assert code == 200 and ctype.startswith("text/plain")
        assert body.strip().splitlines()[1:] == debug_help_text().splitlines()
        for p, params, desc in DEBUG_ENDPOINTS:
            assert p + params in body
        # ... and so is the handler docstring (the in-code help block)
        doc = server.http.RequestHandlerClass._debug_get.__doc__
        assert debug_help_text() in doc
        # unknown debug paths get the index alongside the error
        code, _, body = _get(port, "/debug/bogus")
        assert code == 404 and "endpoints" in json.loads(body)
    finally:
        server.stop()


def test_debug_kernels_disabled_serves_enabled_false():
    from kubernetes_tpu.server import SchedulerServer

    api = FakeCluster()
    sched = Scheduler(
        configuration=SchedulerConfiguration(kernel_ledger=False)
    )
    api.connect(sched)
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    server.start()
    try:
        code, _, body = _get(server.port, "/debug/kernels")
        assert code == 200 and json.loads(body) == {"enabled": False}
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# planner visibility (satellite: dispatch.plan / harvest.plan + flight)
# ---------------------------------------------------------------------------


def test_planner_spans_and_flight_event():
    from kubernetes_tpu.planner import run_planner

    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    for n in _nodes(4):
        api.create_node(n)
    for i in range(6):
        api.create_pod(_pod(f"w{i}"))
    sched.schedule_pending()
    for i in range(3):
        api.create_pod(_pod(f"back{i}", cpu="64"))  # a pending backlog
    sched.tracer.start()
    out = run_planner(sched, "autoscale", {"max_count": "2"})
    sched.tracer.stop()
    assert "error" not in out
    names = {
        e.get("name") for e in sched.tracer.export()["traceEvents"]
    }
    assert {"dispatch.plan", "harvest.plan"} <= names
    events = sched.flight.events_for("planner")
    assert events and events[-1]["kind"] == "plan"
    assert events[-1]["detail"]["planner"] == "autoscale"
    assert events[-1]["detail"]["forks"] >= 1
    # per-kernel d2h attribution covered the planner's readback
    row = next(
        r
        for r in sched.kernels.table(cost=False)
        if r["kernel"] == "counterfactual.counterfactual_run"
    )
    assert row["d2h_bytes"] > 0
    # the serial engine leaves its own span + breadcrumb
    from kubernetes_tpu.planner import plan as plan_mod

    pp = sched.queue.pending_pods()
    pending = pp["active"] + pp["unschedulable"] + pp["backoff"]
    sched.tracer.start()
    sim = plan_mod.simulate_forks(
        sched,
        [plan_mod.Fork(label="baseline")],
        pending[:1],
        planner="custom",
        use_kernel=False,
    )
    sched.tracer.stop()
    assert sim.engine == "serial"
    names = {
        e.get("name") for e in sched.tracer.export()["traceEvents"]
    }
    assert "plan.serial" in names
    events = sched.flight.events_for("planner")
    assert events[-1]["detail"]["engine"] == "serial"
