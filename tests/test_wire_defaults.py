"""The wire carries an object WITHOUT the fields that stand at their declared
default (api/codec.py, WIRE.md "Fields at their default"; PR 45).

What is held here:
  * ``decode(encode(x)) == x`` with the same TYPES all the way down, for every
    kind of ``codec.KINDS`` and for pods built from every template of
    ``benchmarks/configs/*.json`` (plain, spread, required and preferred
    (anti-)affinity), bound and unbound;
  * compatibility both ways: a FULL payload — every field present, as the codec
    wrote it before this convention (``_full_wire`` below is that writer, kept
    as the reference) — decodes to the same object, and the trimmed payload is
    a subset of it, key for key;
  * a field with no default is always on the wire; a non-default value that is
    falsy (``0``, ``""``, ``{}``, ``False``, ``0.0``) comes back as it went;
  * the event frame of a bound ``pod-default`` is under 60 % of the full one;
  * the served path: an idempotent create still answers 200 for a replayed spec
    and 409 for another once the server has written ``node_name``, and a JSON
    and a binary watcher of one stream decode equal pods after a bulk bind.
"""

import dataclasses
import glob
import json
import os
import time
import typing

import pytest

from benchmarks import workload
from kubernetes_tpu.api import codec, types as T
from kubernetes_tpu.api import resource as R
from kubernetes_tpu.api.codec import KINDS, decode, encode, from_wire, to_wire
from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.client import wire_codec
from kubernetes_tpu.client.api_server import ApiServer
from kubernetes_tpu.client.client import ApiClient, ApiError
from kubernetes_tpu.testing.fake_cluster import FakeCluster

CONFIG_DIR = os.path.join(os.path.dirname(workload.__file__), "configs")


def _full_wire(obj):
    """The dataclass tree with EVERY field present: what ``to_wire`` wrote
    before PR 45, and what a journal, a fixture or an older peer still holds."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (list, tuple)):
        return [_full_wire(x) for x in obj]
    if isinstance(obj, dict):
        return {str(k): _full_wire(v) for k, v in obj.items()}
    return {f.name: _full_wire(getattr(obj, f.name)) for f in dataclasses.fields(obj)}


def _same(a, b, where="x"):
    """Equal AND of the same type at every level (``==`` alone lets ``0`` pass
    for ``False``, ``1.0`` for ``1``, a list for nothing but a list — but a
    tuple that came back a list would compare unequal only at the top)."""
    assert type(a) is type(b), f"{where}: {type(a).__name__} came back {type(b).__name__}"
    if dataclasses.is_dataclass(a):
        for f in dataclasses.fields(a):
            _same(getattr(a, f.name), getattr(b, f.name), f"{where}.{f.name}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), where
        for i, (x, y) in enumerate(zip(a, b)):
            _same(x, y, f"{where}[{i}]")
    elif isinstance(a, dict):
        assert list(a) == list(b), where
        for k in a:
            _same(a[k], b[k], f"{where}[{k!r}]")
    else:
        assert a == b, f"{where}: {a!r} came back {b!r}"


def _subset(trimmed, full, where="object"):
    """Every key the trimmed payload carries is in the full one with the same
    value: the trimmed payload is the full one less some fields, nothing else."""
    if isinstance(trimmed, dict):
        assert isinstance(full, dict) and set(trimmed) <= set(full), where
        for k, v in trimmed.items():
            _subset(v, full[k], f"{where}.{k}")
    elif isinstance(trimmed, list):
        assert len(trimmed) == len(full), where
        for i, (x, y) in enumerate(zip(trimmed, full)):
            _subset(x, y, f"{where}[{i}]")
    else:
        assert type(trimmed) is type(full) and trimmed == full, where


def _through_both_codecs(envelope):
    via_binary = wire_codec.decode_frame(wire_codec.encode_frame(envelope))[0]
    via_json = json.loads(json.dumps(envelope))
    assert via_binary == via_json == envelope
    return via_binary, via_json


def _check_round_trip(obj):
    env = encode(obj)
    for wire in _through_both_codecs(env):
        _same(obj, decode(wire))
    full = {"kind": env["kind"], "object": _full_wire(obj)}
    _subset(env["object"], full["object"])
    for wire in _through_both_codecs(full):
        _same(obj, decode(wire))


# ---------------------------------------------------------------------------
# every kind
# ---------------------------------------------------------------------------

_TERM = T.PodAffinityTerm(
    topology_key="kubernetes.io/hostname",
    label_selector=T.LabelSelector(
        match_labels={"color": "green"},
        match_expressions=(T.LabelSelectorRequirement("tier", "In", ("a", "b")),),
    ),
    namespaces=("sched-1", "sched-0"),
)

KIND_SAMPLES = {
    "Pod": [
        T.Pod(name="bare"),
        T.Pod(name="bound", uid="default/bound", node_name="n1", phase="Running", start_time=12.5),
        T.Pod(
            name="rich",
            namespace="sched-1",
            uid="sched-1/rich",
            labels={"app": "rich", "empty": ""},
            annotations={"note": ""},
            priority=7,
            preemption_policy="Never",
            containers=[
                T.Container(
                    name="c",
                    requests={"cpu": "250m", "memory": 128 * 2**20, "tpu.dev/chips": 1.5},
                    limits={"cpu": "1"},
                    ports=(T.ContainerPort(container_port=80, host_port=8080),),
                ),
                T.Container(),
            ],
            init_containers=[T.Container(name="init", restart_policy="Always")],
            overhead={"cpu": "10m"},
            node_selector={"disk": "ssd"},
            affinity=T.Affinity(
                node_affinity=T.NodeAffinity(
                    required_during_scheduling_ignored_during_execution=T.NodeSelector(
                        node_selector_terms=(
                            T.NodeSelectorTerm(
                                match_expressions=(T.NodeSelectorRequirement("zone", "In", ("a",)),)
                            ),
                        )
                    ),
                    preferred_during_scheduling_ignored_during_execution=(
                        T.PreferredSchedulingTerm(weight=3, preference=T.NodeSelectorTerm()),
                    ),
                ),
                pod_affinity=T.PodAffinity(required_during_scheduling_ignored_during_execution=(_TERM,)),
                pod_anti_affinity=T.PodAntiAffinity(
                    preferred_during_scheduling_ignored_during_execution=(
                        T.WeightedPodAffinityTerm(weight=1, pod_affinity_term=_TERM),
                    )
                ),
            ),
            tolerations=(T.Toleration(), T.Toleration(key="k", operator="Exists", toleration_seconds=0)),
            topology_spread_constraints=(
                T.TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=T.LabelSelector(match_labels={"color": "blue"}),
                    min_domains=0,
                ),
            ),
            scheduling_gates=("gate",),
            volumes=(T.Volume(name="v", pvc_name="claim", read_only=True), T.Volume()),
            resource_claims=("claim-a",),
            pod_group="gang",
            host_network=True,
            images=("img:1",),
            nominated_node_name="n9",
            deletion_timestamp=0.0,
        ),
    ],
    "Node": [
        T.Node(name="bare"),
        T.Node(
            name="n0",
            labels={"kubernetes.io/hostname": "n0", "custom/λ": "ünï"},
            annotations={"a": ""},
            capacity=Resource.from_map({"cpu": "8", "memory": "32Gi", "pods": 110, "tpu.dev/chips": 4}),
            taints=(T.Taint("dedicated", "tpu", "NoSchedule"), T.Taint("bare")),
            unschedulable=True,
            images={"img:1": 0, "img:2": 12345},
            ready=False,
            last_heartbeat=17.25,
        ),
        # allocatable set apart from capacity (a reservation)
        T.Node(
            name="reserved",
            capacity=Resource.from_map({"cpu": "4", "memory": "32Gi", "pods": 110}),
            allocatable=Resource.from_map({"cpu": "3500m", "memory": "30Gi", "pods": 100}),
        ),
    ],
    "Resource": [
        Resource(),
        Resource.from_map({"cpu": "100m", "memory": "64Mi"}),
        Resource(milli_cpu=0, memory=1, scalars={"x/y": 0}),
    ],
    "PodDisruptionBudget": [
        T.PodDisruptionBudget(name="bare"),
        T.PodDisruptionBudget(
            name="pdb",
            namespace="sched-0",
            selector=T.LabelSelector(match_labels={"app": "p0"}),
            disruptions_allowed=1,
        ),
    ],
}


def test_samples_cover_every_kind():
    assert set(KIND_SAMPLES) == set(KINDS)
    for kind, samples in KIND_SAMPLES.items():
        assert all(type(s) is KINDS[kind] for s in samples)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_kind_round_trips_with_its_types(kind):
    for obj in KIND_SAMPLES[kind]:
        _check_round_trip(obj)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_bare_object_carries_only_what_has_no_default(kind):
    """The first sample of each kind sets nothing but what it must: what is
    left on the wire is the fields without a default (and a pod's uid, which
    ``__post_init__`` draws)."""
    obj = KIND_SAMPLES[kind][0]
    required = {
        f.name
        for f in dataclasses.fields(obj)
        if f.default is dataclasses.MISSING and f.default_factory is dataclasses.MISSING
    }
    drawn = {"uid"} if kind == "Pod" else set()
    assert set(encode(obj)["object"]) == required | drawn


def _dataclasses_reachable():
    seen, out = set(), []
    stack = list(KINDS.values())
    while stack:
        cls = stack.pop()
        if cls in seen or not dataclasses.is_dataclass(cls):
            continue
        seen.add(cls)
        out.append(cls)

        def walk(hint):
            if dataclasses.is_dataclass(hint):
                stack.append(hint)
            for a in getattr(hint, "__args__", ()) or ():
                walk(a)

        for hint in typing.get_type_hints(cls).values():
            walk(hint)
    return sorted(out, key=lambda c: c.__name__)


@pytest.mark.parametrize("cls", _dataclasses_reachable(), ids=lambda c: c.__name__)
def test_a_field_with_no_default_is_always_on_the_wire(cls):
    """Built with the emptiest legal value for each required field — ``""``,
    ``0``, the nested class's own emptiest — the required fields are all there,
    and the object comes back."""

    def emptiest(c):
        hints = typing.get_type_hints(c)
        kw = {}
        for f in dataclasses.fields(c):
            if f.default is dataclasses.MISSING and f.default_factory is dataclasses.MISSING:
                h = hints[f.name]
                kw[f.name] = emptiest(h) if dataclasses.is_dataclass(h) else h()
        return c(**kw)

    obj = emptiest(cls)
    wire = to_wire(obj)
    for f in dataclasses.fields(cls):
        if f.default is dataclasses.MISSING and f.default_factory is dataclasses.MISSING:
            assert f.name in wire
    _same(obj, from_wire(json.loads(json.dumps(wire)), cls))
    _same(obj, from_wire(_full_wire(obj), cls))


# ---------------------------------------------------------------------------
# falsy is not default
# ---------------------------------------------------------------------------

FALSY_CASES = [
    # (object, path to the value, the value that must be ON the wire)
    (T.Pod(name="p", uid="u", labels={"k": ""}), ("labels",), {"k": ""}),
    (T.Pod(name="p", uid="u", deletion_timestamp=0.0), ("deletion_timestamp",), 0.0),
    (T.Pod(name="p", uid="u", start_time=0.0), ("start_time",), 0.0),
    (T.Pod(name="p", uid="u", overhead={}), ("overhead",), {}),
    (T.Pod(name="p", uid="u", namespace=""), ("namespace",), ""),
    (T.Pod(name="p", uid="u", scheduler_name=""), ("scheduler_name",), ""),
    (T.Pod(name="p", uid="u", phase=""), ("phase",), ""),
    (T.Node(name="n", ready=False), ("ready",), False),
    (T.Container(restart_policy=""), ("restart_policy",), ""),
    (T.Container(requests={}), ("requests",), {}),
    (T.Toleration(operator="", toleration_seconds=0), ("toleration_seconds",), 0),
    (T.Taint("k", effect=""), ("effect",), ""),
    (T.ContainerPort(protocol=""), ("protocol",), ""),
    (
        T.TopologySpreadConstraint(max_skew=0, topology_key="", when_unsatisfiable="", min_domains=0),
        ("min_domains",),
        0,
    ),
    (T.LabelSelector(match_labels={}), ("match_labels",), {}),
]


@pytest.mark.parametrize("obj,path,want", FALSY_CASES, ids=lambda v: None)
def test_a_falsy_value_that_is_not_the_default_stays(obj, path, want):
    wire = to_wire(obj)
    got = wire
    for k in path:
        got = got[k]
    assert type(got) is type(want) and got == want
    _same(obj, from_wire(json.loads(json.dumps(wire)), type(obj)))


def test_a_default_is_matched_by_type_as_well_as_value():
    """``priority`` 0 is the default and stays off; ``False`` in its place, or
    ``1.0`` where the default is ``1``, equals the default and is NOT it."""
    assert "priority" not in to_wire(T.Pod(name="p", uid="u", priority=0))
    assert to_wire(T.Pod(name="p", uid="u", priority=False))["priority"] is False
    assert "host_network" not in to_wire(T.Pod(name="p", uid="u", host_network=False))
    assert to_wire(T.Pod(name="p", uid="u", host_network=0))["host_network"] == 0
    assert "last_heartbeat" not in to_wire(T.Node(name="n", last_heartbeat=0.0))
    assert to_wire(T.Node(name="n", last_heartbeat=0))["last_heartbeat"] == 0
    assert "tolerations" not in to_wire(T.Pod(name="p", uid="u", tolerations=()))
    assert to_wire(T.Pod(name="p", uid="u", tolerations=[]))["tolerations"] == []
    # a default_factory field is compared with what its factory makes
    assert "labels" not in to_wire(T.Pod(name="p", uid="u", labels={}))
    assert "capacity" not in to_wire(T.Node(name="n", capacity=Resource()))
    assert to_wire(T.Node(name="n", capacity=Resource(memory=1)))["capacity"] == {"memory": 1}


def test_memoised_state_on_a_pod_stays_off_the_wire():
    pod = T.Pod(name="p", uid="u", containers=[T.Container(requests={"cpu": "1"})])
    before = to_wire(pod)
    pod.compute_requests(), pod.non_zero_requests(), pod.pvc_names()
    assert any(k.startswith("_") for k in pod.__dict__)
    assert to_wire(pod) == before and not any(k.startswith("_") for k in before)


def test_the_plans_are_made_once_a_class():
    decode(encode(T.Pod(name="p", uid="u", containers=[T.Container()])))
    plan_e, plan_d = codec._ENCODE_PLANS[T.Pod], codec._DECODE_PLANS[T.Pod]
    decode(encode(T.Pod(name="q", uid="v", containers=[T.Container()])))
    assert codec._ENCODE_PLANS[T.Pod] is plan_e and codec._DECODE_PLANS[T.Pod] is plan_d
    # a factory's product in the plan is the plan's own: never handed out
    (default_labels,) = [d for name, _, d in plan_e if name == "labels"]
    got = decode(encode(T.Pod(name="r", uid="w")))
    got.labels["x"] = "y"
    assert default_labels == {} and got.labels is not default_labels
    assert decode(encode(T.Pod(name="s", uid="z"))).labels == {}


def test_static_strings_are_what_the_field_names_make_them():
    """The static intern table is derived from field NAMES, not from what an
    object happens to carry: every field of every reachable dataclass is in it,
    trimmed payloads or not."""
    for cls in _dataclasses_reachable():
        for f in dataclasses.fields(cls):
            assert f.name in wire_codec.STATIC_STRINGS


# ---------------------------------------------------------------------------
# the benchmark's templates
# ---------------------------------------------------------------------------


def _config(config: str) -> dict:
    with open(os.path.join(CONFIG_DIR, f"{config}.json")) as f:
        return json.load(f)


def _templates():
    out = []
    for path in sorted(glob.glob(os.path.join(CONFIG_DIR, "*.json"))):
        cfg = _config(os.path.basename(path)[: -len(".json")])
        for name in cfg["pod_templates"]:
            out.append((cfg["name"], name))
    return out


def _template_spec(config: str, template: str) -> dict:
    return workload.pod_specs(_config(config), template, 1, "measure", "sched-1")[0]


def _template_pod(config: str, template: str, node_name: str):
    return workload.build_pod(T, _template_spec(config, template), node_name=node_name)


def test_every_template_shape_is_among_the_cases():
    names = {t for _, t in _templates()}
    assert {
        "pod-default",
        "pod-with-topology-spreading",
        "pod-with-pod-affinity",
        "pod-with-pod-anti-affinity",
        "pod-with-preferred-pod-affinity",
        "pod-with-preferred-pod-anti-affinity",
    } <= names


@pytest.mark.parametrize("bound", [False, True], ids=["unbound", "bound"])
@pytest.mark.parametrize("config,template", _templates())
def test_template_pods_round_trip(config, template, bound):
    pod = _template_pod(config, template, "node-17" if bound else "")
    _check_round_trip(pod)
    wire = encode(pod)["object"]
    assert ("node_name" in wire) is bound
    # what the template sets stays on the wire, with the other fields gone
    for field in ("affinity", "topology_spread_constraints", "labels"):
        assert (field in wire) is bool(getattr(pod, field))
    assert len(wire) <= 8 < len(dataclasses.fields(T.Pod))


@pytest.mark.parametrize("config,template", _templates())
def test_template_nodes_and_pods_equal_the_reference_types_after_the_wire(config, template):
    """The frozen reference builds its own objects from the same spec; a pod
    that crossed the wire still equals, field by field, what the reference's
    types module builds (``correct`` reads LIST back into specs that way)."""
    from benchmarks.reference import types as RT

    spec = _template_spec(config, template)
    got = decode(json.loads(json.dumps(encode(workload.build_pod(T, spec, node_name="n3")))))
    want = workload.build_pod(RT, spec, node_name="n3")
    assert _full_wire(got) == _full_wire(want)
    node_spec = workload.node_specs(_config(config))[0]
    node = decode(json.loads(json.dumps(encode(workload.build_node(T, R, node_spec)))))
    _same(workload.build_node(T, R, node_spec), node)


def test_bound_pod_default_event_frame_is_under_six_tenths_of_the_full_one():
    pod = _template_pod("sched-perf-basic-5k", "pod-default", "node-4999")
    trimmed = wire_codec.encode_event("MODIFIED", 12345, wire_codec.encode_nested(encode(pod)))
    full = wire_codec.encode_event(
        "MODIFIED", 12345, wire_codec.encode_nested({"kind": "Pod", "object": _full_wire(pod)})
    )
    assert len(trimmed) < 0.6 * len(full), (len(trimmed), len(full))
    line_trimmed = json.dumps({"type": "MODIFIED", "rv": 12345, "object": encode(pod)})
    line_full = json.dumps({"type": "MODIFIED", "rv": 12345, "object": {"kind": "Pod", "object": _full_wire(pod)}})
    assert len(line_trimmed) < 0.6 * len(line_full)
    assert decode(wire_codec.decode_frame(trimmed)[0]["object"]) == pod
    assert decode(wire_codec.decode_frame(full)[0]["object"]) == pod


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def _wait(predicate, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return predicate()


@pytest.fixture()
def served():
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    try:
        yield api, server, f"http://127.0.0.1:{server.port}"
    finally:
        server.stop()


@pytest.mark.parametrize("codec_name", ["binary", "json"])
def test_idempotent_create_after_the_server_wrote_node_name(served, codec_name):
    """A create replayed after the server has written status fields answers
    200 (the same spec), another spec under the same uid 409 — the comparison
    (``ApiServer._spec_wire``) drops the status fields from payloads that may
    or may not carry them."""
    api, server, endpoint = served
    client = ApiClient(endpoint, codec=codec_name)
    client.create_node(T.Node(name="n0", capacity=Resource.from_map({"cpu": "4", "memory": "32Gi", "pods": 110})))
    pod = _template_pod("sched-perf-spread-5k", "pod-with-topology-spreading", "")
    assert client._req("POST", "/api/v1/pods", encode(pod)) == {"ok": True}
    assert client.bind_many([(pod, "n0")]) == [None]
    client.patch_pod_phase(pod.uid, "Running")
    stored = api.pods[pod.uid]
    assert stored.node_name == "n0" and stored.phase == "Running"
    # the replay: the spec as first sent, and as a FULL payload (an older peer)
    assert client._req("POST", "/api/v1/pods", encode(pod)) == {"ok": True, "idempotent": True}
    full = {"kind": "Pod", "object": _full_wire(pod)}
    assert client._req("POST", "/api/v1/pods", full) == {"ok": True, "idempotent": True}
    # another spec under the uid: a field moved OFF its default, and one moved ONTO it
    for other in (
        dataclasses.replace(pod, priority=5),
        dataclasses.replace(pod, labels={}),
        dataclasses.replace(pod, topology_spread_constraints=()),
    ):
        with pytest.raises(ApiError) as e:
            client._req("POST", "/api/v1/pods", encode(other))
        assert e.value.code == 409
    assert api.pods[pod.uid] is stored
    # nodes: the heartbeat is status, a label is spec
    node = T.Node(name="n0", capacity=Resource.from_map({"cpu": "4", "memory": "32Gi", "pods": 110}))
    client.patch_node_status("n0", ready=False, heartbeat=99.5)
    assert client._req("POST", "/api/v1/nodes", encode(node)) == {"ok": True, "idempotent": True}
    with pytest.raises(ApiError) as e:
        client._req("POST", "/api/v1/nodes", encode(dataclasses.replace(node, labels={"zone": "a"})))
    assert e.value.code == 409


def test_json_and_binary_watchers_decode_equal_pods_after_a_bulk_bind(served):
    api, server, endpoint = served
    bc, jc = ApiClient(endpoint, codec="binary"), ApiClient(endpoint, codec="json")
    bc.create_nodes(
        [T.Node(name=f"n{i}", capacity=Resource.from_map({"cpu": "4", "memory": "32Gi", "pods": 110})) for i in range(3)]
    )
    pods = [
        dataclasses.replace(_template_pod(cfg, tpl, ""), name=f"w-{i}", uid=f"sched-1/w-{i}")
        for i, (cfg, tpl) in enumerate(_templates())
    ]
    bc.create_pods(pods)
    head = server.caches["pods"].rv
    assert bc.bind_many([(p, f"n{i % 3}") for i, p in enumerate(pods)]) == [None] * len(pods)

    def take(client, n):
        out = []
        for evt in client.watch_stream("pods", head):
            if evt.get("type") != "BOOKMARK":
                out.append(evt)
            if len(out) >= n:
                break
        return out

    via_binary, via_json = take(bc, len(pods)), take(jc, len(pods))
    assert via_binary == via_json
    assert {e["type"] for e in via_binary} == {"MODIFIED"}
    want = [dataclasses.replace(p, node_name=f"n{i % 3}") for i, p in enumerate(pods)]
    for evt_b, evt_j, pod in zip(via_binary, via_json, want):
        _same(pod, decode(evt_b["object"]))
        _same(pod, decode(evt_j["object"]))
    # LIST reads back what was bound, through either codec and the spliced blobs
    for client in (bc, jc):
        listed = {p.uid: p for p in map(decode, client.list("pods")["items"])}
        for pod in want:
            _same(pod, listed[pod.uid])
    # the counter that says the wire engaged: bytes moved, by codec
    with server._wire_mu:
        assert server.wire_bytes[("binary", "tx")] > 0 and server.wire_bytes[("json", "tx")] > 0
