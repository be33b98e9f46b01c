"""Regression tests for the assume-copy protocol and mirror overflow repack."""

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, ContainerPort, Node, Pod, Taint
from kubernetes_tpu.cache import Cache, SnapshotMirror
from kubernetes_tpu.snapshot.interner import PAD


def _node(name):
    return Node(
        name=name,
        capacity=Resource.from_map({"cpu": "8", "memory": "32Gi", "pods": 110}),
    )


def test_assume_does_not_mutate_queued_pod():
    """schedule_one.go assumes a DeepCopy; a failed attempt must leave the
    queued object pristine."""
    cache = Cache()
    cache.add_node(_node("n1"))
    pod = Pod(name="p", containers=[Container(name="c", requests={"cpu": "1"})])
    cache.assume_pod(pod, "n1")
    assert pod.node_name == "", "assume mutated the caller's pod"
    assert cache.is_assumed(pod.uid)
    cache.forget_pod(pod)
    assert pod.node_name == ""
    assert not cache.is_assumed(pod.uid)
    # the pod can be assumed again on a different node
    cache.assume_pod(pod, "n1")
    assert pod.node_name == ""


def test_assume_then_informer_confirm():
    cache = Cache()
    cache.add_node(_node("n1"))
    pod = Pod(name="p")
    cache.assume_pod(pod, "n1")
    confirmed = Pod(name="p", uid=pod.uid, node_name="n1")
    cache.add_pod(confirmed)
    assert not cache.is_assumed(pod.uid)
    assert pod.node_name == ""  # queued object still untouched


def _port_pod(name, node, port):
    return Pod(
        name=name,
        node_name=node,
        containers=[
            Container(name="c", ports=(ContainerPort(host_port=port),))
        ],
    )


def test_mirror_port_overflow_repacks_same_cycle():
    """Host-port rows beyond the bucket must be visible to THIS batch, not
    the next one."""
    cache = Cache()
    cache.add_node(_node("n1"))
    cache.add_pod(_port_pod("a", "n1", 8000))
    mirror = SnapshotMirror()
    mirror.update(cache)
    u0 = mirror.nodes.used_ppk.shape[1]
    # add more port pods than the current bucket holds
    for i in range(u0 + 2):
        cache.add_pod(_port_pod(f"b{i}", "n1", 9000 + i))
    mirror.update(cache)
    row = mirror.nodes.used_ppk[mirror.nodes.name_to_idx["n1"]]
    n_rows = int((row != PAD).sum())
    assert n_rows == u0 + 3, f"snapshot missing port rows: {n_rows} != {u0 + 3}"


def test_mirror_taint_overflow_repacks_same_cycle():
    """A node update adding more taints than the bucket holds must repack
    so the device filter sees every taint."""

    cache = Cache()
    n = _node("n1")
    cache.add_node(n)
    cache.add_node(_node("n2"))
    mirror = SnapshotMirror()
    mirror.update(cache)
    t_cap = mirror.nodes.taint_key.shape[1]
    taints = tuple(Taint(key=f"k{i}", value="v") for i in range(t_cap + 2))
    n2 = Node(name="n1", capacity=n.capacity, taints=taints)
    cache.update_node(n2)
    mirror.update(cache)
    row = mirror.nodes.taint_key[mirror.nodes.name_to_idx["n1"]]
    n_taints = int((row != PAD).sum())
    assert n_taints == t_cap + 2, f"snapshot dropped taints: {n_taints}"
