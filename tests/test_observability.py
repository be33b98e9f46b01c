"""Observability layer: span tracer, flight recorder, explain mode,
debug endpoints, and the metrics-exposition hardening that rode along.

Covers the PR-4 acceptance surface:
  * span export round-trips as valid Chrome trace JSON with correctly
    nested ts/dur;
  * flight-recorder ring eviction under overflow;
  * explain output matches the host oracle's rejection reasons on a
    mixed feasible/infeasible batch (per node, per plugin);
  * the debug endpoints serve well-formed JSON through the real HTTP
    server;
  * a DISABLED tracer is a no-op (no events, no device-path cost);
  * /metrics exposition survives concurrent writes, escapes label
    values, and rejects duplicate metric registration.

Plus the PR-7 steady-state SLO tier:
  * per-stage attribution reconciles with a synthetic flight-recorder
    event stream;
  * an SLO breach freezes the black-box ring and auto-dumps a
    Perfetto-loadable trace whose window covers the breach;
  * /debug/slo serves the live SLI snapshot schema;
  * black-box mode off is a no-op (one attribute read per site);
  * Histogram.percentile returns the +Inf sentinel at saturation.
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Affinity,
    Container,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    PodAntiAffinity,
    Taint,
    TopologySpreadConstraint,
)
from kubernetes_tpu.observability import (
    FlightRecorder,
    Tracer,
    explain_pod,
    find_pod,
    oracle_explain,
)
from kubernetes_tpu.scheduler import Scheduler


def _mk_sched():
    s = Scheduler()
    bound = {}
    s.binding_sink = lambda pod, node: bound.__setitem__(pod.uid, node)
    return s, bound


def _nodes(n=4, cpu="2", zones=2, taint_every=0):
    out = []
    for i in range(n):
        taints = ()
        if taint_every and i % taint_every == 0:
            taints = (Taint(key="dedicated", value="infra"),)
        out.append(
            Node(
                name=f"n{i}",
                labels={
                    "kubernetes.io/hostname": f"n{i}",
                    "topology.kubernetes.io/zone": f"zone-{i % zones}",
                },
                capacity=Resource.from_map({"cpu": cpu, "memory": "4Gi"}),
                taints=taints,
            )
        )
    return out


def _pod(name, cpu="100m", mem="64Mi", **kw):
    return Pod(
        name=name,
        containers=[Container(requests={"cpu": cpu, "memory": mem})],
        **kw,
    )


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_export_valid_and_nested():
    tr = Tracer()
    tr.start()
    with tr.span("outer", kind="test"):
        time.sleep(0.002)
        with tr.span("inner"):
            time.sleep(0.002)
        time.sleep(0.002)
    tr.stop()
    out = tr.export()
    # round-trips as JSON
    loaded = json.loads(json.dumps(out))
    evs = loaded["traceEvents"]
    by_name = {e["name"]: e for e in evs if e.get("ph") == "X"}
    assert set(by_name) == {"outer", "inner"}
    outer, inner = by_name["outer"], by_name["inner"]
    for e in (outer, inner):
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] > 0
    # correctly nested: inner strictly inside outer on the same track
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"]["kind"] == "test"
    # metadata present for Perfetto track naming
    assert any(e.get("ph") == "M" and e["name"] == "thread_name" for e in evs)


def test_tracer_disabled_is_noop():
    tr = Tracer()
    assert not tr.enabled
    # the disabled span is a shared singleton — no allocation, no events
    s1, s2 = tr.span("a"), tr.span("b")
    assert s1 is s2
    with s1:
        pass
    tr.complete("x", 0.0)
    tr.complete_tail("y", 0.5)
    tr.instant("z")
    assert tr.stats()["events"] == 0


def test_scheduler_drain_traces_only_when_enabled():
    s, bound = _mk_sched()
    for n in _nodes(3):
        s.on_node_add(n)
    for i in range(4):
        s.on_pod_add(_pod(f"p{i}"))
    s.schedule_pending()
    assert s.tracer.stats()["events"] == 0  # disabled by default

    s.tracer.start()
    for i in range(4, 8):
        s.on_pod_add(_pod(f"p{i}"))
    s.schedule_pending()
    s.tracer.stop()
    evs = s.tracer.export()["traceEvents"]
    names = {e["name"] for e in evs}
    assert "drain" in names
    # phase spans from the PhaseAccumulator hook + batch spans with ids
    assert any(e.get("cat") == "phase" for e in evs)
    batch = [e for e in evs if e.get("cat") == "batch"]
    assert batch and all(e["args"]["bid"] >= 1 for e in batch)
    drain = next(e for e in evs if e["name"] == "drain")
    assert drain["args"]["scheduled"] == 4


def test_tracer_bounded_buffer_drops():
    tr = Tracer(max_events=5)
    tr.start()
    for i in range(9):
        tr.instant(f"e{i}")
    st = tr.stats()
    assert st["events"] == 5 and st["dropped"] == 4


def test_tracer_logical_time_from_journal():
    from kubernetes_tpu.chaos.journal import Journal, JournalRecorder

    s, bound = _mk_sched()
    journal = Journal()
    rec = JournalRecorder(journal)
    rec.attach(s)
    s.tracer.start()
    for n in _nodes(2):
        s.on_node_add(n)
    s.on_pod_add(_pod("p0"))
    s.schedule_pending()
    s.tracer.stop()
    evs = s.tracer.export()["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    assert spans and all("lt" in e["args"] for e in spans)
    # deliveries were journaled before the drain ran, so the drain span's
    # logical time is at least the delivery count
    drain = next(e for e in spans if e["name"] == "drain")
    assert drain["args"]["lt"] >= 3
    # detach restores the handlers and stops stamping logical time
    lt_before = journal.now()
    rec.detach()
    assert s.tracer.logical_time is None
    s.on_pod_add(_pod("post-detach"))
    assert journal.now() == lt_before  # no longer journaled
    s.tracer.start()
    s.schedule_pending()
    s.tracer.stop()
    post = [
        e
        for e in s.tracer.export()["traceEvents"]
        if e.get("ph") == "X"
    ]
    assert post and all("lt" not in e["args"] for e in post)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_eviction_under_overflow():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record(f"pod-{i % 4}", "enqueue", {"i": i})
    st = fr.stats()
    assert st["events"] == 8
    assert st["recorded_total"] == 20
    assert st["evicted_total"] == 12
    # the ring kept the NEWEST events
    tail = fr.tail(100)
    assert [e["detail"]["i"] for e in tail] == list(range(12, 20))
    # per-uid query scans only retained events
    assert [e["detail"]["i"] for e in fr.events_for("pod-0")] == [12, 16]


def test_flight_recorder_disabled_records_nothing():
    fr = FlightRecorder()
    fr.enabled = False
    fr.record("u", "enqueue")
    assert fr.stats()["events"] == 0


def test_pod_lifecycle_events_scheduled_and_unschedulable():
    s, bound = _mk_sched()
    for n in _nodes(3):
        s.on_node_add(n)
    ok = _pod("ok")
    big = _pod("big", cpu="64", mem="100Gi")
    s.on_pod_add(ok)
    s.on_pod_add(big)
    s.schedule_pending()
    ok_kinds = [e["kind"] for e in s.flight.events_for(ok.uid)]
    assert ok_kinds[:3] == ["enqueue", "pop", "assumed"]
    assert ok_kinds[-1] == "bound"
    big_kinds = [e["kind"] for e in s.flight.events_for(big.uid)]
    assert big_kinds[0] == "enqueue"
    assert "unschedulable" in big_kinds and "requeue" in big_kinds
    unsched = next(
        e for e in s.flight.events_for(big.uid) if e["kind"] == "unschedulable"
    )
    assert "NodeResourcesFit" in (unsched["detail"]["plugins"] or [])


# ---------------------------------------------------------------------------
# explain mode vs the host oracle
# ---------------------------------------------------------------------------


def _assert_explain_matches_oracle(s, pod):
    fwk = s.profiles[pod.scheduler_name or "default-scheduler"]
    ex = explain_pod(s, pod, max_nodes=10_000)
    ora = oracle_explain(pod, s.oracle_view(), fwk.device_enabled())
    kernel = {n: set(v) for n, v in ex["nodes"].items()}
    oracle = {n: set(v) for n, v in ora.items()}
    assert kernel == oracle, f"{pod.name}: kernel={kernel} oracle={oracle}"
    return ex


def test_explain_matches_oracle_mixed_batch():
    s, bound = _mk_sched()
    # 4 nodes: n0/n2 zone-0, n1/n3 zone-1; n0 tainted; small cpu
    for n in _nodes(4, cpu="2", zones=2, taint_every=4):
        s.on_node_add(n)
    # placed pods: group=g on n1 (anti-affinity target), app=x skewed
    # onto zone-0 (spread violation there)
    s.on_pod_add(
        Pod(
            name="placed-g",
            node_name="n1",
            labels={"group": "g"},
            containers=[Container(requests={"cpu": "100m"})],
        )
    )
    for i, node in enumerate(("n0", "n2")):
        s.on_pod_add(
            Pod(
                name=f"placed-x{i}",
                node_name=node,
                labels={"app": "x"},
                containers=[Container(requests={"cpu": "100m"})],
            )
        )

    feasible = _pod("feasible")
    big = _pod("big", cpu="64", mem="100Gi")
    named = _pod("named")
    named.node_name = "n2"
    anti = Pod(
        name="anti",
        labels={"group": "g"},
        affinity=Affinity(
            pod_anti_affinity=PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=(
                    PodAffinityTerm(
                        topology_key="kubernetes.io/hostname",
                        label_selector=LabelSelector(
                            match_labels={"group": "g"}
                        ),
                    ),
                )
            )
        ),
        containers=[Container(requests={"cpu": "100m"})],
    )
    spread = Pod(
        name="spread",
        labels={"app": "x"},
        topology_spread_constraints=(
            TopologySpreadConstraint(
                max_skew=1,
                topology_key="topology.kubernetes.io/zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "x"}),
            ),
        ),
        containers=[Container(requests={"cpu": "100m"})],
    )

    for pod in (feasible, big, named, anti, spread):
        ex = _assert_explain_matches_oracle(s, pod)
        assert ex["n_feasible"] == len(ex["feasible"])
    # spot checks on the rendered verdicts
    ex_big = explain_pod(s, big, max_nodes=100)
    assert ex_big["n_feasible"] == 0
    assert ex_big["summary"]["NodeResourcesFit"] == 4
    assert "TaintToleration" in ex_big["nodes"]["n0"]
    ex_named = explain_pod(s, named)
    assert set(ex_named["feasible"]) == {"n2"}
    assert ex_named["nodes"]["n0"].count("NodeName") == 1
    ex_anti = explain_pod(s, anti)
    assert "InterPodAffinity" in ex_anti["nodes"]["n1"]
    assert "n1" not in ex_anti["feasible"]
    ex_spread = explain_pod(s, spread)
    assert "PodTopologySpread" in ex_spread["nodes"]["n0"]
    assert "PodTopologySpread" in ex_spread["nodes"]["n2"]
    assert set(ex_spread["feasible"]) >= {"n3"}


def test_explain_truncation_and_summary_cover_all_nodes():
    s, bound = _mk_sched()
    for n in _nodes(8, cpu="1"):
        s.on_node_add(n)
    big = _pod("big", cpu="32")
    ex = explain_pod(s, big, max_nodes=3)
    assert len(ex["nodes"]) == 3 and ex["truncated"]
    assert ex["summary"]["NodeResourcesFit"] == 8  # summary is uncapped


def test_find_pod_resolves_queue_and_cache():
    s, bound = _mk_sched()
    for n in _nodes(2):
        s.on_node_add(n)
    big = _pod("big", cpu="64")
    s.on_pod_add(big)
    s.schedule_pending()  # parks unschedulable
    assert find_pod(s, "big").uid == big.uid
    assert find_pod(s, big.uid).uid == big.uid
    assert find_pod(s, "nope") is None


# ---------------------------------------------------------------------------
# debug endpoints over the real HTTP server
# ---------------------------------------------------------------------------


def _get_json(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as r:
            assert r.headers["Content-Type"].startswith("application/json")
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        assert e.headers["Content-Type"].startswith("application/json")
        return e.code, json.loads(e.read().decode())


def test_debug_endpoints_serve_json():
    from kubernetes_tpu.server import SchedulerServer
    from kubernetes_tpu.testing.fake_cluster import FakeCluster

    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    for n in _nodes(3):
        api.create_node(n)
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    server.start()
    try:
        port = server.port
        # trace lifecycle through the endpoint
        code, st = _get_json(port, "/debug/trace?action=start")
        assert code == 200 and st["enabled"]
        api.create_pod(_pod("served"))
        api.create_pod(_pod("stuck", cpu="64"))
        deadline = time.time() + 10
        while time.time() < deadline:
            if sched.flight.events_for(
                find_pod(sched, "stuck").uid
                if find_pod(sched, "stuck")
                else ""
            ):
                kinds = [
                    e["kind"]
                    for e in sched.flight.events_for(find_pod(sched, "stuck").uid)
                ]
                if "requeue" in kinds:
                    break
            time.sleep(0.05)
        code, st = _get_json(port, "/debug/trace?action=stop")
        assert code == 200 and not st["enabled"]
        code, trace = _get_json(port, "/debug/trace?action=export")
        assert code == 200 and isinstance(trace["traceEvents"], list)
        assert any(e.get("name") == "drain" for e in trace["traceEvents"])
        # flight recorder: stats + per-pod query by NAME
        code, stats = _get_json(port, "/debug/flightrecorder")
        assert code == 200 and stats["events"] > 0 and "tail" in stats
        code, fr = _get_json(port, "/debug/flightrecorder?pod=stuck")
        assert code == 200
        assert any(e["kind"] == "unschedulable" for e in fr["events"])
        # explain for the unschedulable pod, by name
        code, ex = _get_json(port, "/debug/explain?pod=stuck")
        assert code == 200
        assert ex["summary"].get("NodeResourcesFit") == 3
        assert all("NodeResourcesFit" in v for v in ex["nodes"].values())
        # acceptance: same rejecting plugins per node as the host oracle
        stuck = find_pod(sched, "stuck")
        ora = oracle_explain(
            stuck,
            sched.oracle_view(),
            sched.profiles["default-scheduler"].device_enabled(),
        )
        assert {n: set(v) for n, v in ex["nodes"].items()} == {
            n: set(v) for n, v in ora.items()
        }
        # errors are JSON too
        code, err = _get_json(port, "/debug/explain?pod=missing-pod")
        assert code == 404 and "error" in err
        code, err = _get_json(port, "/debug/explain")
        assert code == 400 and "error" in err
        code, err = _get_json(port, "/debug/trace?action=bogus")
        assert code == 400 and "error" in err
        code, err = _get_json(port, "/debug/explain?pod=stuck&max_nodes=abc")
        assert code == 400 and "error" in err
        # legacy /debug/cache text route still serves
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/cache", timeout=10
        ) as r:
            assert r.status == 200 and b"cache dump" in r.read()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# metrics satellites: exposition race, escaping, duplicate guard
# ---------------------------------------------------------------------------


def test_metrics_expose_survives_concurrent_writes():
    from kubernetes_tpu.metrics import Counter, Gauge, Histogram, Registry

    r = Registry()
    c = r.register(Counter("obs_test_counter_total", "", ("pod",)))
    g = r.register(Gauge("obs_test_gauge", "", ("pod",)))
    h = r.register(Histogram("obs_test_hist", "", ("pod",)))
    stop = threading.Event()
    errors = []

    def hammer():
        i = 0
        while not stop.is_set():
            i += 1
            c.inc(pod=f"p{i}")
            g.set(i, pod=f"p{i}")
            h.observe(0.01, pod=f"p{i}")

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        deadline = time.time() + 0.5
        while time.time() < deadline:
            try:
                r.expose()
                h.percentile(0.99)
            except Exception as e:  # noqa: BLE001 — the regression itself
                errors.append(e)
                break
    finally:
        stop.set()
        t.join(timeout=5)
    assert not errors, f"expose raced a writer: {errors[0]!r}"


def test_label_values_escaped():
    from kubernetes_tpu.metrics import Counter

    c = Counter("obs_escape_total", "", ("reason",))
    c.inc(reason='node(s) said "no"\nline2\\end')
    text = "\n".join(c.expose())
    assert '\\"no\\"' in text
    assert "\\n" in text and "\n".join(c.expose()).count("line2") == 1
    assert "\\\\end" in text
    # the exposition still parses line-by-line (no raw newline inside a label)
    for line in c.expose():
        assert "\n" not in line


def test_registry_rejects_duplicate_names():
    from kubernetes_tpu.metrics import Counter, Registry

    r = Registry()
    r.register(Counter("obs_dup_total", ""))
    with pytest.raises(ValueError):
        r.register(Counter("obs_dup_total", ""))


def test_observability_gauges_on_metrics_endpoint():
    s, bound = _mk_sched()
    for n in _nodes(2):
        s.on_node_add(n)
    s.on_pod_add(_pod("p0"))
    s.schedule_pending()
    text = s.expose_metrics()
    assert "scheduler_tpu_flightrecorder_events" in text
    assert "scheduler_tpu_trace_buffered_events" in text
    assert "scheduler_tpu_tracer_overhead_seconds" in text


# ---------------------------------------------------------------------------
# steady-state SLO tier (observability/slo.py) + black-box ring
# ---------------------------------------------------------------------------


def _slo_cfg(**kw):
    from kubernetes_tpu.observability.slo import SLOConfig, SLOObjective

    defaults = dict(
        objectives=[
            SLOObjective("bind_p99", "bind", 0.99, 1.0),
            SLOObjective("e2e_p99", "e2e", 0.99, 30.0),
        ],
        min_samples=4,
        eval_interval_s=0.0,
        breach_cooldown_s=0.0,
    )
    defaults.update(kw)
    return SLOConfig(**defaults)


def _slo_snapshot_once_filed(slo, timeout=10.0):
    """The snapshot once a detected breach is filed.  The ``slo-eval``
    worker may detect the breach first, on its own cadence: it then takes
    the cooldown under the lock (a manual ``evaluate()`` returns None)
    and files the record only after the freeze, export and dump, outside
    the lock — a snapshot taken in between reads ``breaches_total`` 0."""
    deadline = time.time() + timeout
    while slo.snapshot()["breaches_total"] < 1 and time.time() < deadline:
        time.sleep(0.02)
    return slo.snapshot()


def test_histogram_percentile_overflow_is_inf_sentinel():
    import math

    from kubernetes_tpu.metrics import Histogram, wide_duration_buckets

    h = Histogram("obs_sat_test", "", buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(50.0)  # overflow bucket
    # p50 interpolates inside a finite bucket; p99's rank lands in the
    # overflow bucket and must NOT silently clamp to 1.0
    assert h.percentile(0.5) <= 0.1
    assert math.isinf(h.percentile(0.99))
    # the SLO tier widens its buckets so the sentinel only fires when
    # latency is truly off the scale
    assert wide_duration_buckets()[-1] > 1000.0


def test_slo_attribution_reconciles_with_flight_events():
    """Feed the evaluator a hand-built breadcrumb stream and check every
    stage duration it joins against the arithmetic of the stream."""
    from kubernetes_tpu.observability.slo import SLOEvaluator

    ev = SLOEvaluator(_slo_cfg())
    t = 100.0
    # pod A: clean first-attempt flight
    ev.ingest([(t + 0.0, "A", "enqueue", None)])
    ev.ingest([(t + 1.0, "A", "pop", None)])
    ev.ingest([(t + 1.5, "A", "assumed", None)])
    ev.ingest([(t + 1.7, "A", "bind_start", None)])
    ev.ingest([(t + 2.0, "A", "bound", None)])
    # pod B: fails once (requeue → backoff → re-pop), then binds
    ev.ingest([(t + 0.0, "B", "enqueue", None)])
    ev.ingest([(t + 0.5, "B", "pop", None)])
    ev.ingest([(t + 0.6, "B", "unschedulable", {"plugins": ["X"]})])
    ev.ingest([(t + 0.6, "B", "requeue", {"to": "backoff"})])
    ev.ingest([(t + 2.6, "B", "pop", None)])
    ev.ingest([(t + 3.0, "B", "assumed", None)])
    ev.ingest([(t + 3.1, "B", "bind_start", None)])
    ev.ingest([(t + 3.2, "B", "bound", None)])
    h = ev._stage_hist
    # queue_wait: A 1.0, B 0.5 (first pop only)
    assert h.count(stage="queue_wait") == 2
    assert h.total_sum(stage="queue_wait") == pytest.approx(1.5)
    # backoff: B 2.0 (requeue → re-pop)
    assert h.count(stage="backoff") == 1
    assert h.total_sum(stage="backoff") == pytest.approx(2.0)
    # dispatch: A 0.5, B(attempt1) 0.1... no — B's first attempt never
    # reached assumed; B's second pop→assumed is 0.4
    assert h.count(stage="dispatch") == 2
    assert h.total_sum(stage="dispatch") == pytest.approx(0.5 + 0.4)
    # commit: A 0.2, B 0.1
    assert h.total_sum(stage="commit") == pytest.approx(0.3)
    # bind: A 0.3, B 0.1
    assert h.total_sum(stage="bind") == pytest.approx(0.4)
    # e2e: A 2.0, B 3.2
    assert h.count(stage="e2e") == 2
    assert h.total_sum(stage="e2e") == pytest.approx(5.2)
    # terminal events close the open-attempt state
    assert ev.snapshot()["open_attempts"] == 0


def test_slo_vectorized_join_matches_scalar_reference():
    """The worker's vectorized join (coalesced same-kind segments, numpy
    gather/scatter) must produce bit-identical cumulative accounting to
    the scalar reference loop on a randomized lifecycle stream —
    including requeue/backoff cycles, mid-flight joins (pop before any
    enqueue was seen), and bulk runs sharing one stamp."""
    import random

    from kubernetes_tpu.observability.slo import SLOEvaluator, SERIES

    rng = random.Random(1234)
    t = [100.0]

    def tick():
        t[0] += rng.random() * 0.05
        return t[0]

    # build (mono, [(uid, kind, detail)...]) pairs: interleave singleton
    # enqueues with bulk stage runs, some pods failing into backoff
    pairs = []
    flying = []
    for wave in range(6):
        new = [f"w{wave}-p{i}" for i in range(rng.randrange(30, 120))]
        for u in new:
            pairs.append((tick(), [(u, "enqueue", None)]))
        flying.extend(new)
        rng.shuffle(flying)
        batch, flying = flying[:96], flying[96:]
        if not batch:
            continue
        m = tick()
        pairs.append((m, [(u, "pop", None) for u in batch]))
        fail = [u for u in batch if rng.random() < 0.25]
        ok = [u for u in batch if u not in fail]
        if fail:
            m = tick()
            pairs.append(
                (m, [(u, "unschedulable", {"plugins": ["X"]}) for u in fail])
            )
            pairs.append((tick(), [(u, "requeue", {"to": "backoff"}) for u in fail]))
            flying.extend(fail)  # re-pop next wave
        if ok:
            pairs.append((tick(), [(u, "assumed", None) for u in ok]))
            pairs.append((tick(), [(u, "bind_start", None) for u in ok]))
            pairs.append((tick(), [(u, "bound", None) for u in ok]))
    # a pod the tier never saw enqueue for (armed mid-flight)
    pairs.append((tick(), [("midflight", "pop", None)]))
    pairs.append((tick(), [("midflight", "assumed", None)]))
    pairs.append((tick(), [("midflight", "bound", None)]))

    ref = SLOEvaluator(_slo_cfg(eval_interval_s=3600.0))
    vec = SLOEvaluator(_slo_cfg(eval_interval_s=3600.0))
    for mono, events in pairs:
        ref.ingest([(mono, u, k, d) for u, k, d in events])
    with vec._mu:
        vec._join_pairs_locked(pairs)
    for s in SERIES:
        rc, rsum, rn = ref._slo_cum[s]
        vc, vsum, vn = vec._slo_cum[s]
        assert rn == vn, (s, rn, vn)
        assert list(rc) == list(vc), s
        assert rsum == pytest.approx(vsum, abs=1e-9)
        assert list(ref._win_cur[s]) == list(vec._win_cur[s]), s
    for ro, vo in zip(ref._slo_objs, vec._slo_objs):
        assert (ro.n_cur, ro.bad_cur) == (vo.n_cur, vo.bad_cur)
    assert len(ref._slo_idx) == len(vec._slo_idx)
    assert set(ref._slo_idx) == set(vec._slo_idx)


def test_slo_attribution_on_real_drain_matches_ring():
    """On a real scheduled batch, the joined stage durations must
    reconcile with the mono stamps retained in the flight-recorder ring."""
    s, bound = _mk_sched()
    s.install_slo(_slo_cfg())
    for n in _nodes(3):
        s.on_node_add(n)
    pods = [_pod(f"sp{i}") for i in range(6)]
    for p in pods:
        s.on_pod_add(p)
    s.schedule_pending()
    s.slo.flush()  # read-your-writes barrier for the async sink
    s.slo.gauge_rows()  # sync the registry histogram
    h = s.slo._stage_hist
    assert h.count(stage="e2e") == 6
    assert h.count(stage="dispatch") == 6
    for p in pods:
        evs = {e["kind"]: e["mono"] for e in s.flight.events_for(p.uid)}
        assert {"enqueue", "pop", "assumed", "bind_start", "bound"} <= set(evs)
        assert evs["enqueue"] <= evs["pop"] <= evs["assumed"] <= evs["bound"]
    # the cumulative e2e sum equals the per-pod ring deltas (same stamps)
    ring_e2e = sum(
        next(e["mono"] for e in s.flight.events_for(p.uid) if e["kind"] == "bound")
        - next(e["mono"] for e in s.flight.events_for(p.uid) if e["kind"] == "enqueue")
        for p in pods
    )
    assert h.total_sum(stage="e2e") == pytest.approx(ring_e2e, abs=1e-6)


def test_slo_breach_freezes_and_dumps_blackbox_ring(tmp_path):
    """An impossible SLO during a throttled run must auto-dump a
    Perfetto-loadable black-box trace whose window covers the breach —
    with nobody having started a capture."""
    from kubernetes_tpu.observability.slo import SLOObjective

    s, bound = _mk_sched()
    s.install_slo(
        _slo_cfg(
            objectives=[SLOObjective("bind_p99", "bind", 0.99, 1e-9)],
            dump_dir=str(tmp_path),
            # one breach only: the ring frozen MID-DRAIN holds the spans
            # of the window leading up to it (a cooldown of 0 would dump
            # and re-arm repeatedly, leaving the last ring near-empty)
            breach_cooldown_s=3600.0,
        )
    )
    assert s.tracer.stats()["mode"] == "blackbox"
    for n in _nodes(3):
        s.on_node_add(n)
    for i in range(12):
        s.on_pod_add(_pod(f"bb{i}"))
    s.schedule_pending()
    s.slo.evaluate()  # settle any cadence race — breach is deterministic
    snap = _slo_snapshot_once_filed(s.slo)
    assert snap["breaches_total"] >= 1
    rec = snap["last_breach"]
    assert rec["objective"] == "bind_p99"
    assert rec["measured_s"] > rec["threshold_s"]
    assert rec["window_samples"] >= 4
    assert rec["burn_rate"] > 1.0
    # the artifact was dumped without any manual capture and parses as a
    # Chrome trace whose events all precede the freeze point
    assert rec["trace"] and os.path.exists(rec["trace"])
    with open(rec["trace"]) as f:
        trace = json.load(f)
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert evs, "ring dump contains no spans"
    for e in evs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["ts"] + e["dur"] <= rec["breach_offset_us"] + 1e4
    # the ring re-armed itself for the next incident
    assert s.tracer.stats()["mode"] == "blackbox"
    assert s.tracer.enabled
    # with the artifact on disk the export is NOT also pinned in memory
    assert s.slo.last_breach_trace() is None


def test_breach_dump_failure_falls_back_and_keeps_tier_alive(tmp_path):
    """An unwritable dump_dir must not kill the breach path (or the
    worker thread it runs on): the record files with trace=None, the
    export is retained in memory instead, the ring re-arms, and the
    error is counted."""
    from kubernetes_tpu.observability.slo import SLOObjective

    blocker = tmp_path / "not-a-dir"
    blocker.write_text("a file where makedirs expects a directory")
    s, bound = _mk_sched()
    s.install_slo(
        _slo_cfg(
            objectives=[SLOObjective("bind_p99", "bind", 0.99, 1e-9)],
            dump_dir=str(blocker),
            breach_cooldown_s=3600.0,
        )
    )
    for n in _nodes(2):
        s.on_node_add(n)
    for i in range(8):
        s.on_pod_add(_pod(f"df{i}"))
    s.schedule_pending()
    s.slo.evaluate()
    snap = _slo_snapshot_once_filed(s.slo)
    assert snap["breaches_total"] == 1
    assert snap["last_breach"]["trace"] is None
    assert snap["ingest_errors"] >= 1
    # the in-memory fallback serves what the disk couldn't take
    assert s.slo.last_breach_trace() is not None
    # and the tier is still alive: ring re-armed, evaluation still runs
    assert s.tracer.stats()["mode"] == "blackbox" and s.tracer.enabled
    assert s.slo.evaluate() is None  # cooldown holds; no crash


def test_manual_capture_rearms_blackbox_on_export():
    """The documented manual flow (start → stop → export) overrides the
    always-on ring; export is its terminal step and must RE-ARM the ring
    so the breach-dump guarantee survives operator captures."""
    from kubernetes_tpu.server import SchedulerServer
    from kubernetes_tpu.testing.fake_cluster import FakeCluster

    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    sched.install_slo(_slo_cfg())
    assert sched.tracer.stats()["mode"] == "blackbox"
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    server.start()
    try:
        port = server.port
        _get_json(port, "/debug/trace?action=start")
        assert sched.tracer.stats()["mode"] == "capture"
        _get_json(port, "/debug/trace?action=stop")
        code, trace = _get_json(port, "/debug/trace?action=export")
        assert code == 200 and "traceEvents" in trace
        st = sched.tracer.stats()
        assert st["mode"] == "blackbox" and st["enabled"]
    finally:
        server.stop()


def test_blackbox_ring_evicts_oldest():
    tr = Tracer()
    tr.blackbox_start(capacity=5)
    for i in range(9):
        tr.instant(f"e{i}")
    st = tr.stats()
    assert st["mode"] == "blackbox"
    assert st["events"] == 5 and st["evicted"] == 4 and st["dropped"] == 0
    names = [e["name"] for e in tr.export()["traceEvents"] if e.get("ph") == "i"]
    assert names == ["e4", "e5", "e6", "e7", "e8"]  # recent history wins
    # freeze keeps the window and stops recording; manual start() leaves
    # ring mode entirely
    frozen = tr.blackbox_freeze()
    assert not tr.enabled and frozen["freeze_offset_us"] > 0
    tr.start()
    assert tr.stats()["mode"] == "capture"
    assert tr.blackbox_freeze() is None


def test_blackbox_mode_off_is_noop():
    """Without install_slo nothing records: the tracer stays disabled
    (one attribute read per site), the flight recorder has no sink, and
    /debug-visible SLO state reports uninstalled."""
    s, bound = _mk_sched()
    assert s.slo is None
    assert s.flight.sink is None
    for n in _nodes(2):
        s.on_node_add(n)
    for i in range(4):
        s.on_pod_add(_pod(f"nb{i}"))
    s.schedule_pending()
    st = s.tracer.stats()
    assert st["events"] == 0 and st["evicted"] == 0
    assert not s.tracer.enabled
    # installing with blackbox=False attributes latency but records no spans
    s2, _ = _mk_sched()
    s2.install_slo(_slo_cfg(blackbox=False))
    for n in _nodes(2):
        s2.on_node_add(n)
    s2.on_pod_add(_pod("nb-attr"))
    s2.schedule_pending()
    assert s2.tracer.stats()["events"] == 0
    assert not s2.tracer.enabled
    s2.slo.flush()  # read-your-writes barrier for the async sink
    s2.slo.gauge_rows()  # sync the registry histogram
    assert s2.slo._stage_hist.count(stage="e2e") == 1


def test_debug_slo_endpoint_schema():
    from kubernetes_tpu.server import SchedulerServer
    from kubernetes_tpu.testing.fake_cluster import FakeCluster

    api = FakeCluster()
    sched = Scheduler()
    api.connect(sched)
    for n in _nodes(3):
        api.create_node(n)
    server = SchedulerServer(sched, ground_truth=api.ground_truth)
    server.start()
    try:
        port = server.port
        # uninstalled: explicit "not enabled" body, still JSON
        code, body = _get_json(port, "/debug/slo")
        assert code == 200 and body == {"enabled": False}
        sched.install_slo(_slo_cfg())
        api.create_pod(_pod("slo-pod"))
        deadline = time.time() + 10
        while time.time() < deadline:
            if sched.slo._stage_hist.count(stage="e2e") >= 1:
                break
            time.sleep(0.05)
        code, snap = _get_json(port, "/debug/slo")
        assert code == 200
        assert snap["enabled"] is True
        assert {"objectives", "stages", "breaches_total", "last_breach",
                "blackbox", "window_s"} <= set(snap)
        for o in snap["objectives"]:
            assert {"name", "series", "quantile", "threshold_s",
                    "current_s", "burn_rate", "window_samples",
                    "breached"} <= set(o)
        for stage in ("queue_wait", "backoff", "dispatch", "commit",
                      "bind", "e2e"):
            st = snap["stages"][stage]
            assert {"count", "sum_s", "p50_s", "p99_s"} <= set(st)
        assert snap["stages"]["e2e"]["count"] >= 1
        assert snap["blackbox"]["mode"] == "blackbox"
        # no breach yet → trace action 404s with a JSON error
        code, err = _get_json(port, "/debug/slo?action=trace")
        assert code == 404 and "error" in err
        code, err = _get_json(port, "/debug/slo?action=bogus")
        assert code == 400 and "error" in err
        # burn-rate gauge rides the scrape
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            text = r.read().decode()
        assert "scheduler_tpu_slo_burn_rate" in text
        assert "scheduler_tpu_slo_stage_duration_seconds" in text
    finally:
        server.stop()


def test_sli_duration_immune_to_queue_clock_jumps():
    """The e2e SLI derives from the monotonic enqueue stamp: a manual /
    wall clock jumping forward 1e6 s between enqueue and drain must not
    smear the latency histogram (satellite: scheduler.py computed it on
    the injectable clock before)."""
    now = [1000.0]
    s = Scheduler(clock=lambda: now[0])
    bound = {}
    s.binding_sink = lambda pod, node: bound.__setitem__(pod.uid, node)
    for n in _nodes(2):
        s.on_node_add(n)
    s.on_pod_add(_pod("jump"))
    now[0] += 1e6  # the clock jump
    s.schedule_pending()
    assert bound
    h = s.prom.pod_scheduling_sli_duration
    assert h.count(attempts="1") == 1
    assert h.total_sum(attempts="1") < 60.0  # real seconds, not the 1e6 jump


def test_attempt_duration_carries_batch_size_label():
    s, bound = _mk_sched()
    for n in _nodes(3):
        s.on_node_add(n)
    for i in range(4):
        s.on_pod_add(_pod(f"bl{i}"))
    s.schedule_pending()
    text = s.expose_metrics()
    line = next(
        l for l in text.splitlines()
        if l.startswith("scheduler_scheduling_attempt_duration_seconds_bucket")
    )
    assert 'batch="' in line
    from kubernetes_tpu.metrics import batch_size_bucket

    assert batch_size_bucket(1) == "1"
    assert batch_size_bucket(4) == "2-15"
    assert batch_size_bucket(100) == "16-255"
    assert batch_size_bucket(5000) == "4096+"
