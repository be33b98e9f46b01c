"""The allocator policy (``kubernetes_tpu/util/allocator.py``): engaged once a
process at the package's import, a no-op on a libc without ``mallopt``,
invisible to decisions; the count ``alloc.sys_grown_mb`` and the three
scraped series.

glibc fixes its arena limit once and keeps the arenas it has made; a pytest
worker has threads (and two arenas) before it imports the package, so what
the policy does to the arenas is observed in fresh interpreters
(``_CHILD`` below, in the order ``benchmarks/runner.py`` imports), one with
the policy and one whose libc shows no ``mallopt``.
"""

import json
import os
import pathlib
import subprocess
import sys
import urllib.request

import pytest

import kubernetes_tpu
from kubernetes_tpu.metrics import Histogram, PhaseAccumulator, SchedulerMetrics
from kubernetes_tpu.util import allocator

REPO = pathlib.Path(kubernetes_tpu.__file__).parent.parent
DRAIN_PODS = 2000

# A fresh interpreter: backend up, then two drains of DRAIN_PODS assumed pods
# each from a FRESH thread (the second after the first's pods are forgotten),
# then the served 48-pod backlog of tests/test_server.py.
_CHILD = r"""
import json, os, sys, threading
mode = sys.argv[1]
import ctypes
real_cdll = ctypes.CDLL
if mode == "no_mallopt":
    class NoMallopt:
        def __init__(self, *a, **k):
            self.__dict__["_lib"] = real_cdll(*a, **k)
        def __getattr__(self, name):
            if name == "mallopt":
                raise AttributeError(name)
            return getattr(self._lib, name)
    ctypes.CDLL = NoMallopt
import jax
import kubernetes_tpu
ctypes.CDLL = real_cdll
jax.devices()
import jax.numpy as jnp
(jnp.arange(8) + 1).block_until_ready()

from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import Container, Node, Pod
from kubernetes_tpu.cache.cache import Cache
from kubernetes_tpu.metrics import PhaseAccumulator
from kubernetes_tpu.util import allocator

libc = real_cdll(None)
out = {"engaged": allocator.engaged(), "arenas": [allocator._arenas(libc)], "grown_mb": []}
cache = Cache()
for i in range(50):
    cache.add_node(Node(name=f"n{i}", capacity=Resource.from_map({"cpu": "64", "memory": "512Gi"})))
phases = PhaseAccumulator()
watch = allocator.HeapWatch(phases)
watch.sample()
for drain in range(2):
    pods = [
        Pod(name=f"d{drain}-{i}", containers=[Container(requests={"cpu": "100m", "memory": "500Mi"})])
        for i in range(int(sys.argv[2]))
    ]
    pairs = [(p, f"n{i % 50}") for i, p in enumerate(pods)]
    watch.sample()
    before = phases.snapshot().get("alloc.sys_grown_mb", 0.0)
    held = []
    t = threading.Thread(target=lambda: held.extend(cache.assume_pods_bulk(pairs)))
    t.start(); t.join()
    assert len(held) == len(pods) and not [h for h in held if isinstance(h, str)]
    watch.sample()
    out["grown_mb"].append(phases.snapshot().get("alloc.sys_grown_mb", 0.0) - before)
    out["arenas"].append(allocator._arenas(libc))
    for p in held:
        cache.forget_pod(p)
    del held, pods, pairs

sys.path.insert(0, sys.argv[3])
from test_server import _served_backlog_bindings
out["bindings"] = _served_backlog_bindings()
print(json.dumps(out), flush=True)
os._exit(0)  # daemon threads are still up: no interpreter teardown under them
"""


@pytest.fixture(scope="module")
def children():
    """{mode: the child's report}; the two run side by side."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    procs = {
        mode: subprocess.Popen(
            [sys.executable, "-c", _CHILD, mode, str(DRAIN_PODS), str(REPO / "tests")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for mode in ("policy", "no_mallopt")
    }
    reports = {}
    for mode, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-2000:]
        reports[mode] = json.loads(stdout.splitlines()[-1])
    return reports


class _FakeLibc:
    """A libc that takes every ``mallopt`` and remembers it."""

    def __init__(self, answer=1):
        self.calls = []
        self.answer = answer

    def mallopt(self, param, value):
        self.calls.append((param, value))
        return self.answer


@pytest.fixture
def fresh(monkeypatch):
    """The module as a process finds it before its first engagement."""
    monkeypatch.setattr(allocator, "_engaged", None)
    return monkeypatch


def test_the_package_engaged_the_policy_at_import():
    assert allocator.engaged() is True
    assert allocator.engage() is True


def test_engaging_twice_is_one_engagement(fresh):
    libc = _FakeLibc()
    fresh.setattr(allocator, "_lookup", lambda: libc)
    assert allocator.engage() is True and allocator.engage() is True
    assert libc.calls == [(allocator.M_ARENA_MAX, 1)]
    assert allocator.engaged() is True


def test_the_policy_is_one_arena_and_nothing_else():
    # <malloc.h>'s number; the thresholds stay glibc's own (dynamic)
    assert (allocator.M_ARENA_MAX, allocator.ARENA_MAX) == (-8, 1)
    assert not [n for n in vars(allocator) if n.startswith("M_") and n != "M_ARENA_MAX"]


def test_a_refused_parameter_is_not_an_engagement(fresh):
    libc = _FakeLibc(answer=0)
    fresh.setattr(allocator, "_lookup", lambda: libc)
    assert allocator.engage() is False and allocator.engaged() is False
    assert allocator.engage() is False and len(libc.calls) == 1  # tried once


def test_a_libc_without_mallopt_is_a_silent_no_op(fresh):
    fresh.setattr(allocator, "_lookup", lambda: None)
    assert allocator.engage() is False and allocator.engage() is False
    assert allocator.engaged() is False
    phases = PhaseAccumulator()
    watch = allocator.HeapWatch(phases)
    watch.sample()
    watch.sample()
    watch.sync_registry(SchedulerMetrics())
    assert phases.snapshot() == {}


@pytest.mark.parametrize("missing", ["gnu_get_libc_version", "mallopt", "the library"])
def test_lookup_decides_by_what_the_libc_shows(monkeypatch, missing):
    import ctypes

    class Lib:
        def __getattr__(self, name):
            if name == missing:
                raise AttributeError(name)
            return object()

    def cdll(name):
        if missing == "the library":
            raise OSError("no such library")
        return Lib()

    monkeypatch.setattr(ctypes, "CDLL", cdll)
    assert allocator._lookup() is None


def test_an_older_glibc_without_mallinfo2_or_malloc_info_books_nothing():
    class Old:
        def __getattr__(self, name):
            raise AttributeError(name)

    assert allocator._system_bytes(Old()) is None and allocator._arenas(Old()) is None
    phases = PhaseAccumulator()
    watch = allocator.HeapWatch(phases)
    watch._libc = Old()
    watch.sample()
    watch.sample()
    assert phases.snapshot() == {}


def _watch_reading(monkeypatch, totals):
    """A HeapWatch whose libc reports ``totals`` one after another, as
    (arena, hblkhd) pairs in bytes."""
    it = iter(totals)
    monkeypatch.setattr(allocator, "_system_bytes", lambda libc: next(it))
    phases = PhaseAccumulator(hist=Histogram("t_phase_seconds", "test", ("phase",)))
    phases.tracer = _Tap()
    return allocator.HeapWatch(phases), phases


class _Tap:
    def __init__(self):
        self.calls = []

    enabled = True

    def complete_tail(self, *a, **k):
        self.calls.append((a, k))


def test_the_count_is_a_count_no_histogram_no_span(monkeypatch):
    mb = 1 << 20
    watch, phases = _watch_reading(
        monkeypatch, [(10 * mb, 0), (12 * mb, mb), (12 * mb, mb), (40 * mb, 0)]
    )
    watch.sample()
    watch.sample()  # + 2 MiB of heap, + 1 MiB mapped
    assert phases.snapshot() == {"alloc.sys_grown_mb": 3.0}
    watch.sample()  # nothing moved: nothing booked
    watch.sample()  # + 27 MiB
    assert phases.snapshot() == {"alloc.sys_grown_mb": 30.0}
    assert phases.hist.count(phase="alloc.sys_grown_mb") == 0 and phases.tracer.calls == []
    assert PhaseAccumulator.diff(phases.snapshot(), {"alloc.sys_grown_mb": 3.0}) == {
        "alloc.sys_grown_mb": 27.0
    }


def test_memory_handed_back_books_nothing_and_lowers_the_base(monkeypatch):
    mb = 1 << 20
    watch, phases = _watch_reading(monkeypatch, [(50 * mb, 0), (34 * mb, 0), (50 * mb, 0)])
    watch.sample()
    watch.sample()  # trimmed by 16 MiB
    assert phases.snapshot() == {}
    watch.sample()  # grown again by the same 16: obtained from the kernel twice
    assert phases.snapshot() == {"alloc.sys_grown_mb": 16.0}


def test_the_first_sample_is_the_base_and_books_nothing(monkeypatch):
    mb = 1 << 20
    watch, phases = _watch_reading(monkeypatch, [(80 * mb, 0), (81 * mb, 0)])
    watch.sample()  # a base, nothing to compare with
    assert phases.snapshot() == {}
    watch.sample()
    assert phases.snapshot() == {"alloc.sys_grown_mb": 1.0}


def test_the_loops_own_sample_is_taken_at_most_once_in_sample_every_s(monkeypatch):
    """``mallinfo2`` holds every arena's lock while it walks the free lists:
    the loop asks after every busy iteration, the watch answers once in
    SAMPLE_EVERY_S; start and stop always read."""
    mb = 1 << 20
    clock = [100.0]
    monkeypatch.setattr(allocator.time, "monotonic", lambda: clock[0])
    watch, phases = _watch_reading(monkeypatch, [(10 * mb, 0), (11 * mb, 0), (14 * mb, 0)])
    watch.sample()  # the loop starts
    for _ in range(50):  # fifty chained batches within the interval: no reading
        clock[0] += allocator.SAMPLE_EVERY_S / 100
        watch.sample(due_only=True)
    assert phases.snapshot() == {}
    clock[0] += allocator.SAMPLE_EVERY_S
    watch.sample(due_only=True)
    assert phases.snapshot() == {"alloc.sys_grown_mb": 1.0}
    watch.sample()  # stop(): at once, whatever the clock says
    assert phases.snapshot() == {"alloc.sys_grown_mb": 4.0}
    assert 0.5 <= allocator.SAMPLE_EVERY_S <= 5.0


def test_the_watch_reads_this_process(monkeypatch):
    """Real glibc: a block over every threshold is obtained from the kernel
    and the next sample books it."""
    phases = PhaseAccumulator()
    watch = allocator.HeapWatch(phases)
    watch.sample()
    block = bytearray(300 << 20)  # over any mmap threshold
    watch.sample()
    assert phases.snapshot()["alloc.sys_grown_mb"] >= 200.0
    del block


def _env():
    from kubernetes_tpu.api.resource import Resource
    from kubernetes_tpu.api.types import Node
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.testing.fake_cluster import FakeCluster

    api, sched = FakeCluster(), Scheduler()
    api.connect(sched)
    api.create_node(Node(name="n0", capacity=Resource.from_map({"cpu": "8", "memory": "16Gi"})))
    return api, sched


SERIES = (
    "scheduler_tpu_malloc_system_bytes",
    "scheduler_tpu_malloc_mmapped_bytes",
    "scheduler_tpu_malloc_arenas",
)


@pytest.mark.parametrize("series", SERIES)
def test_the_scrape_serves_the_series(series):
    from kubernetes_tpu.server import SchedulerServer

    api, sched = _env()
    server = SchedulerServer(sched)
    server.start()
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{server.port}/metrics", timeout=5) as r:
            body = r.read().decode()
    finally:
        server.stop()
    line = [ln for ln in body.splitlines() if ln.startswith(series + " ")]
    assert line, series
    value = float(line[0].split()[1])
    if series == "scheduler_tpu_malloc_system_bytes":
        assert value > 1 << 20
    elif series == "scheduler_tpu_malloc_arenas":
        assert value >= 1
    else:
        assert value >= 0


def test_two_servers_share_the_policy_and_each_counts_for_its_own_scheduler(monkeypatch):
    from kubernetes_tpu.server import SchedulerServer

    mb = 1 << 20
    total = [100 * mb]
    monkeypatch.setattr(allocator, "_system_bytes", lambda libc: (total[0], 0))
    (_, s1), (_, s2) = _env(), _env()
    a, b = SchedulerServer(s1), SchedulerServer(s2)
    a.start()
    b.start()
    try:
        total[0] += 5 * mb
    finally:
        a.stop()
        b.stop()
    assert allocator.engaged() is True
    assert s1.phases.snapshot()["alloc.sys_grown_mb"] == 5.0
    assert s2.phases.snapshot()["alloc.sys_grown_mb"] == 5.0


def test_a_scheduler_without_a_server_samples_nothing():
    from kubernetes_tpu.api.types import Container, Pod

    api, sched = _env()
    for i in range(10):
        api.create_pod(Pod(name=f"p{i}", containers=[Container(requests={"cpu": "100m"})]))
    sched.schedule_pending()
    sched.wait_for_bindings()
    assert len(api.bindings) == 10
    assert "alloc.sys_grown_mb" not in sched.phases.snapshot()


# ---- fresh interpreters -------------------------------------------------------


def test_with_the_policy_the_process_keeps_one_arena_under_a_fresh_threads_drain(children):
    got = children["policy"]
    assert got["engaged"] is True
    # backend up, then a fresh thread's drain, then another: where it was
    assert got["arenas"] == [1, 1, 1]


def test_with_the_policy_a_second_drain_obtains_nothing_from_the_kernel(children):
    first, second = children["policy"]["grown_mb"]
    # 1.6 KB a pod came from the heap the backend's start-up had grown, or
    # grew it once; the second drain reuses what the first one freed
    assert 0.0 <= first < 0.004 * DRAIN_PODS
    assert second < 0.0001 * DRAIN_PODS


def test_without_mallopt_the_package_imports_and_the_backend_makes_its_arenas(children):
    got = children["no_mallopt"]
    assert got["engaged"] is False
    assert got["arenas"][0] > 1
    # the counter sees what the policy takes away: a fresh thread's arena
    # grown for every drain (1.6 KB a pod), trimmed when its pods are forgotten
    assert sum(got["grown_mb"]) > 0.0005 * DRAIN_PODS


def test_the_policy_is_invisible_to_decisions(children):
    with_policy, without = children["policy"]["bindings"], children["no_mallopt"]["bindings"]
    assert len(with_policy) == 48 and with_policy == without


# ---- nothing a user sets ------------------------------------------------------


def _texts(*dirs):
    for d in dirs:
        for p in sorted((REPO / d).rglob("*")):
            if p.is_file() and p.suffix in {".py", ".sh", ".json", ".md", ".toml", ".txt", ".yaml"}:
                yield p, p.read_text(errors="replace")


def test_no_malloc_environment_name_appears_in_the_program_or_the_benchmark():
    needle = "MALLOC" + "_"
    assert [str(p) for p, text in _texts("kubernetes_tpu", "benchmarks") if needle in text] == []


def test_the_policy_is_no_option():
    import dataclasses

    from kubernetes_tpu.framework import config as cfg

    tpu_options = {
        "fastBatchMax", "fastDeviceMin", "waveDispatch", "residentDrain", "residentRunMax",
        "residentWindow", "residentSerialTail", "gangDispatch", "plannerKernel", "kernelLedger",
        "meshDispatch", "meshPodsAxis",
    }
    upstream = {
        "batchSize", "parallelism", "percentageOfNodesToScore", "podInitialBackoffSeconds",
        "podMaxBackoffSeconds", "referenceSamplingCompat", "tieBreakSeed",
    }
    assert len(tpu_options) == 12 and set(cfg._SCALAR_KEYS) == tpu_options | upstream
    words = ("alloc", "arena", "heap", "trim", "mmap")
    fields = [f.name for f in dataclasses.fields(cfg.SchedulerConfiguration)]
    assert [f for f in fields if any(w in f.lower() for w in words)] == []
    # one module holds the whole policy: nothing else in the package calls mallopt
    callers = [
        str(p.relative_to(REPO)) for p, text in _texts("kubernetes_tpu") if "mallopt" in text
    ]
    assert callers == ["kubernetes_tpu/util/allocator.py"]
