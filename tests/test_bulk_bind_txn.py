"""A bulk binding POST is ONE store transaction (ISSUE 33).

``FakeCluster.bind_many`` applies a slice with ``bind``'s CAS per item and
delivers its updates once: to ``ApiServer``'s batch handler as one
watch-cache append (``_WatchCache.record_many``: one wake-up) and one
``note_api_write_many``; to a subscriber without a batch handler as
``bind``'s per-item ``(old, new)`` copies.  What a client sees — results,
LIST, the watch stream's bytes — is what the per-item route gave.  CPU
only, no device."""

import copy
import threading
import time

import pytest

from kubernetes_tpu.api import types as T
from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.client import ApiClient, ApiServer
from kubernetes_tpu.client.api_server import _WatchCache
from kubernetes_tpu.observability.controlplane import ControlPlaneMonitor
from kubernetes_tpu.testing.fake_cluster import FakeCluster

NODES = ("n0", "n1", "n2")


def _selector(color):
    return T.LabelSelector(match_labels={"color": color})


def _pod(i, shape):
    """The three measured templates' shapes: resource-only, one zone
    spread constraint, one preferred hostname pod-affinity term."""
    kw = {}
    if shape == "spread":
        kw = dict(
            labels={"color": "blue"},
            topology_spread_constraints=(
                T.TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="topology.kubernetes.io/zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=_selector("blue"),
                ),
            ),
        )
    elif shape == "prefaffinity":
        term = T.PodAffinityTerm(
            topology_key="kubernetes.io/hostname",
            label_selector=_selector("red"),
            namespaces=("sched-1", "sched-0"),
        )
        kw = dict(
            labels={"color": "red"},
            affinity=T.Affinity(
                pod_affinity=T.PodAffinity(
                    preferred_during_scheduling_ignored_during_execution=(
                        T.WeightedPodAffinityTerm(weight=1, pod_affinity_term=term),
                    )
                )
            ),
        )
    return T.Pod(
        name=f"p{i}",
        namespace="sched-1",
        uid=f"sched-1/p{i}",
        containers=[T.Container(name="c", requests={"cpu": "100m", "memory": "500Mi"})],
        **kw,
    )


def _cluster(shape, n_pods=8):
    api = FakeCluster(pv_controller=False)
    for name in NODES:
        api.create_node(
            T.Node(name=name, capacity=Resource.from_map({"cpu": "4", "memory": "32Gi", "pods": 110}))
        )
    for i in range(n_pods):
        api.create_pod(_pod(i, shape))
    return api


@pytest.fixture()
def servers():
    """ApiServer over a fresh store, never started: the store's fan-out
    and the watch caches work without the HTTP thread."""
    made = []

    def make(api):
        server = ApiServer(api)
        made.append(server)
        return server

    yield make
    for server in made:
        server.http.server_close()


def _bind_per_item(api, items):
    """The parent commit's bulk route: ``bind`` per item, its exceptions
    turned into the per-item statuses."""
    results = []
    for uid, node in items:
        pod = api.pods.get(uid)
        if pod is None:
            results.append({"code": 404, "error": f"pod {uid} not found"})
            continue
        try:
            api.bind(pod, node)
            results.append(None)
        except RuntimeError as e:
            results.append({"code": 409, "error": str(e), "node": pod.node_name})
        except KeyError as e:
            results.append({"code": 404, "error": str(e)})
    return results


def _mixed_slice():
    """Good items, an unknown pod, an unknown node, a pod bound elsewhere
    (p6, bound to n2 before the slice), a same-node rebind (p7 on n1, and
    p0 a second time inside the slice), a conflict inside the slice."""
    return [
        ("sched-1/p0", "n0"),
        ("sched-1/nope", "n0"),
        ("sched-1/p1", "n1"),
        ("sched-1/p2", "n-unknown"),
        ("sched-1/p6", "n0"),
        ("sched-1/p7", "n1"),
        ("sched-1/p3", "n2"),
        ("sched-1/p0", "n0"),
        ("sched-1/p1", "n2"),
        ("sched-1/p4", "n0"),
    ]


SHAPES = ("basic", "spread", "prefaffinity")


@pytest.mark.parametrize("shape", SHAPES)
class TestSameAsPerItem:
    """(a) one transaction gives what N single binds gave."""

    def _twins(self, servers, shape):
        out = []
        for _ in range(2):
            api = _cluster(shape)
            server = servers(api)
            api.bind(api.pods["sched-1/p6"], "n2")
            api.bind(api.pods["sched-1/p7"], "n1")
            out.append((api, server))
        return out

    def test_results_store_and_bindings(self, servers, shape):
        (one, s_one), (per, _) = self._twins(servers, shape)
        items = _mixed_slice()
        with s_one._mu:
            got = s_one.bind_txn(items)
        want = _bind_per_item(per, items)
        assert got == want
        assert [r and r["code"] for r in got] == [
            None, 404, None, 404, 409, None, None, None, 409, None
        ]
        assert got[4]["node"] == "n2" and got[8]["node"] == "n1"
        assert one.bindings == per.bindings
        assert one.pods == per.pods
        # every acknowledged bind reads back equal through LIST
        # (a pending pod's node_name stands at its default: off the wire)
        listed = {e["object"]["uid"]: e["object"].get("node_name", "") for e in s_one.list_payload("pods")["items"]}
        for (uid, node), result in zip(items, got):
            if result is None:
                assert listed[uid] == node

    def test_watch_cache_frames_are_byte_equal(self, servers, shape):
        (one, s_one), (per, s_per) = self._twins(servers, shape)
        items = _mixed_slice()
        head = s_one.caches["pods"].rv
        assert head == s_per.caches["pods"].rv
        with s_one._mu:
            s_one.bind_txn(items)
        _bind_per_item(per, items)
        ev_one = s_one.caches["pods"].since(head, timeout=0)
        ev_per = s_per.caches["pods"].since(head, timeout=0)
        # one MODIFIED event per bound pod, in item order, none for the
        # two rebinds or the four failures, each with its own rv
        assert [e.envelope["object"]["uid"] for e in ev_one] == [
            "sched-1/p0", "sched-1/p1", "sched-1/p3", "sched-1/p4"
        ]
        assert [e.rv for e in ev_one] == list(range(head + 1, head + 5))
        assert {e.etype for e in ev_one} == {"MODIFIED"}
        assert [e.frame for e in ev_one] == [e.frame for e in ev_per]
        assert [e.json_line for e in ev_one] == [e.json_line for e in ev_per]
        assert s_one.caches["pods"].obj_frames == s_per.caches["pods"].obj_frames
        assert s_one.list_frame("pods") == s_per.list_frame("pods")
        assert s_one.caches["pods"].rv == s_per.caches["pods"].rv == head + 4


class TestPerItemSubscriber:
    """(b) a subscriber that registered no batch handler keeps copy-per-
    event semantics: the aliasing guard create_pod's comment promises."""

    def test_receives_copies_it_may_mutate(self):
        api = _cluster("spread")
        seen = []
        api.watch_pods(lambda p: None, lambda old, new: seen.append((old, new)), lambda p: None)
        results = api.bind_many(
            [("sched-1/p0", "n0"), ("sched-1/p1", "n1"), ("sched-1/p0", "n0")]
        )
        assert results == [None, None, None]
        assert [(o.uid, o.node_name, n.node_name) for o, n in seen] == [
            ("sched-1/p0", "", "n0"),
            ("sched-1/p1", "", "n1"),
        ]
        before = copy.deepcopy(api.pods)
        for old, new in seen:
            assert new is not api.pods[new.uid] and old is not api.pods[old.uid]
            for p in (old, new):
                p.node_name = "scribbled"
                p.labels["color"] = "scribbled"
                p.containers.clear()
        assert api.pods == before
        assert api.bind_many_fallback_items == 2

    def test_batch_subscriber_borrows_the_stored_pods(self):
        api = _cluster("basic")
        calls = []
        api.watch_pods(lambda p: None, pytest.fail, lambda p: None, calls.append)
        api.bind_many([("sched-1/p0", "n0"), ("sched-1/p9", "n0"), ("sched-1/p1", "n1")])
        (pods,) = calls  # ONE call for the slice
        assert [p.uid for p in pods] == ["sched-1/p0", "sched-1/p1"]
        assert all(p is api.pods[p.uid] for p in pods)
        assert api.bind_many_fallback_items == 0
        api.bind_many([("sched-1/p0", "n0")])  # a rebind alone: nothing to deliver
        assert len(calls) == 1

    def test_both_kinds_side_by_side(self, servers):
        """The in-process wiring beside an ApiServer: each subscriber gets
        what it declared, and the server counts the fall-back."""
        api = _cluster("basic")
        server = servers(api)
        seen = []
        api.watch_pods(lambda p: None, lambda old, new: seen.append(new.uid), lambda p: None)
        head = server.caches["pods"].rv
        with server._mu:
            server.bind_txn([("sched-1/p0", "n0"), ("sched-1/p1", "n1")])
        assert seen == ["sched-1/p0", "sched-1/p1"]
        assert server.caches["pods"].rv == head + 2
        assert (server.bulk_bind_txns, server.bulk_bind_items, server.bulk_bind_fallback_items) == (1, 2, 2)


def _filled_cache(window, n):
    cache = _WatchCache(window=window)
    for i in range(n):
        cache.record("ADDED", {"object": {"uid": f"u{i}"}}, key=f"u{i}")
    return cache


class TestSinceReadsTheTail:
    """(c) ``since`` walks the new tail; same answers as the full filter."""

    # window 8 holding rv 5..12 after 12 appends
    @pytest.mark.parametrize(
        "rv", [4, 5, 8, 11, 12, 40], ids=["before", "first", "inside", "last-but-one", "head", "ahead"]
    )
    def test_same_events_as_the_full_filter(self, rv):
        cache = _filled_cache(window=8, n=12)
        want = [e for e in cache.events if e.rv > rv]
        assert cache.since(rv, timeout=0) == want
        assert [e.rv for e in want] == list(range(max(rv, 4) + 1, 13))

    @pytest.mark.parametrize("rv", [0, 3])
    def test_compacted_rv_is_gone(self, rv):
        cache = _filled_cache(window=8, n=12)
        assert cache.since(rv, timeout=0) is None
        assert cache.gone_total == 1

    def test_after_compaction(self):
        cache = _filled_cache(window=8, n=12)
        cache.compact(keep=2)
        assert [e.rv for e in cache.since(10, timeout=0)] == [11, 12]
        assert cache.since(9, timeout=0) is None
        cache.compact(keep=0)
        assert cache.since(12, timeout=0) == []
        assert cache.since(11, timeout=0) is None

    def test_slice_larger_than_the_window_is_gone_for_a_watcher_behind_it(self):
        cache = _filled_cache(window=8, n=2)
        cache.record_many("MODIFIED", [({"object": {"uid": f"u{i}"}}, f"u{i}") for i in range(10)])
        assert cache.rv == 12 and [e.rv for e in cache.events] == list(range(5, 13))
        assert cache.since(2, timeout=0) is None
        assert [e.rv for e in cache.since(4, timeout=0)] == list(range(5, 13))


class TestOneWakeUpASlice:
    """(d) one ``notify_all`` a slice, and the counters that say the
    transaction engaged."""

    def _count_wakeups(self, cache, write, n_events):
        """Wake-ups of a watcher blocked in ``since``: every return from
        the condition's wait, counted on the watcher's own thread."""
        woke = []
        wait = cache.cond.wait
        cache.cond.wait = lambda timeout=None: (wait(timeout), woke.append(1))[0]
        got = []
        blocked = threading.Event()

        def watcher():
            rv = cache.rv
            blocked.set()
            while len(got) < n_events:
                events = cache.since(rv, timeout=5.0)
                if not events:
                    return
                got.extend(events)
                rv = events[-1].rv

        t = threading.Thread(target=watcher, daemon=True)
        t.start()
        assert blocked.wait(5.0)
        deadline = time.monotonic() + 5.0
        while not cache.cond._waiters and time.monotonic() < deadline:
            time.sleep(0.005)
        assert cache.cond._waiters, "the watcher never blocked"
        write()
        t.join(timeout=10.0)
        assert not t.is_alive()
        assert len(got) == n_events
        return len(woke)

    def test_bulk_bind_wakes_a_watcher_once(self, servers):
        api = _cluster("basic", n_pods=64)
        server = servers(api)
        items = [(f"sched-1/p{i}", NODES[i % 3]) for i in range(64)]

        def write():
            with server._mu:
                server.bind_txn(items)

        assert self._count_wakeups(server.caches["pods"], write, 64) == 1
        assert (server.bulk_bind_txns, server.bulk_bind_items, server.bulk_bind_fallback_items) == (1, 64, 0)

    def test_counters_over_http_and_scrape(self):
        """POST /bindings goes through the transaction; the control-plane
        monitor scrapes the three counters with the others."""
        from kubernetes_tpu.scheduler import Scheduler

        api = _cluster("basic")
        server = ApiServer(api).start()
        try:
            client = ApiClient(f"http://127.0.0.1:{server.port}")
            pods = [api.pods[f"sched-1/p{i}"] for i in range(4)]
            assert client.bind_many([(p, "n0") for p in pods]) == [None] * 4
            # the retry of an applied slice: all no-ops, no event
            head = server.caches["pods"].rv
            assert client.bind_many([(p, "n0") for p in pods]) == [None] * 4
            assert server.caches["pods"].rv == head
            errs = client.bind_many([(pods[0], "n1"), (_pod(99, "basic"), "n0")])
            assert errs[0].startswith("HTTP 409") and errs[1].startswith("HTTP 404")
            assert (server.bulk_bind_txns, server.bulk_bind_items, server.bulk_bind_fallback_items) == (3, 10, 0)
            sched = Scheduler()
            sched.install_controlplane(api_server=server)
            text = sched.expose_metrics()
            assert 'scheduler_tpu_apiserver_bulk_bind_total{what="txns"} 3' in text
            assert 'scheduler_tpu_apiserver_bulk_bind_total{what="items"} 10' in text
            assert 'what="fallback_items"' not in text
        finally:
            server.stop()


class TestNoteApiWriteMany:
    """(e) one ``note_api_write_many`` leaves what N ``note_api_write`` do."""

    @pytest.mark.parametrize("rv_window", [4096, 3], ids=["roomy", "window-wraps"])
    def test_same_chains_and_rv_stamps(self, rv_window):
        from kubernetes_tpu.observability.controlplane import ControlPlaneConfig

        pods = [_pod(i, "basic") for i in range(5)]
        node = T.Node(name="n0")  # no uid: joins the rv ring only
        writes = [(10 + i, p) for i, p in enumerate(pods)] + [(15, pods[0])]
        mons = []
        for many in (True, False):
            mon = ControlPlaneMonitor(
                config=ControlPlaneConfig(rv_window=rv_window), mono_clock=lambda: 7.5
            )
            mon.logical_time = lambda: 3
            mon.note_api_write("nodes", 1, node)
            if many:
                mon.note_api_write_many("pods", writes)
            else:
                for rv, obj in writes:
                    mon.note_api_write("pods", rv, obj)
            mons.append(mon)
        one, per = mons
        assert dict(one._open) == dict(per._open)
        assert list(one._open) == list(per._open)  # LRU order too
        assert one._open["sched-1/p0"] == [["api_write", 7.5, 10, 3], ["api_write", 7.5, 15, 3]]
        assert one._rv_stamp == per._rv_stamp
        assert one._rv_order == per._rv_order
        assert one._open.get("n0") is None and list(one._rv_order["nodes"]) == [1]
        assert len(one._rv_order["pods"]) == min(rv_window, 6)
