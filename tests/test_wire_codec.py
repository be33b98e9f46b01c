"""Binary wire codec + zero-copy watch fanout (client/wire_codec.py).

Covers the ISSUE 17 acceptance surface:
  * every registered kind (and the watch-event / list envelopes around
    them) round-trips through the binary frame to an object EQUAL to the
    JSON path's — asserted as byte-identical canonical JSON;
  * the nested-blob splice (encode once, share across the event frame
    and the list frame) decodes identically to direct encoding;
  * HTTP end-to-end: list + watch payloads decode byte-identical under
    binary and JSON clients against the same apiserver, and a client
    that never asks for binary gets JSON (debuggability default);
  * wire-byte accounting lands in scheduler_tpu_wire_bytes_total on
    scrape, split by codec and direction;
  * the condition-variable watch wakeup: an idle watcher blocks, then
    wakes within milliseconds of the append (no 0.5s poll), asserted
    both on _WatchCache.since directly and via the PR 16 watch_fanout
    hop over the real HTTP path;
  * bind retry idempotence: a binding POST applied by the server whose
    response dies on the wire is retried, observes its own first attempt
    as a 409-with-matching-node, and reports success — while a REAL
    conflict still raises;
  * chaos watch-cut/410/compaction scenarios drive identical journals
    under either codec (fault injection sits above the frame seam).
"""

import collections
import enum
import gc
import io
import json
import os
import random
import struct
import threading
import time

import pytest

from kubernetes_tpu.api.codec import KINDS, decode, encode
from kubernetes_tpu.api.resource import Resource
from kubernetes_tpu.api.types import (
    Affinity,
    Container,
    LabelSelector,
    Node,
    NodeAffinity,
    NodeSelector,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    Pod,
    PodDisruptionBudget,
    Taint,
    Toleration,
)
from kubernetes_tpu.client import wire_codec
from kubernetes_tpu.client.api_server import ApiServer
from kubernetes_tpu.client.client import ApiClient, ApiError, RemoteClusterSource
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.testing.fake_cluster import FakeCluster


def _canon(value) -> bytes:
    return json.dumps(value, sort_keys=True, separators=(",", ":")).encode()


def _node(name="n0"):
    return Node(
        name=name,
        labels={
            "kubernetes.io/hostname": name,
            "topology.kubernetes.io/zone": "zone-a",
            "custom/λ-label": "ünïcode",
        },
        capacity=Resource.from_map(
            {"cpu": "8", "memory": "32Gi", "pods": 110, "tpu.dev/chips": 4}
        ),
        taints=(Taint("dedicated", "tpu", "NoSchedule"),),
    )


def _pod(name="p0", uid=""):
    return Pod(
        name=name,
        uid=uid,
        labels={"app": name},
        annotations={"note": ""},
        containers=[
            Container(
                name="c",
                requests={"cpu": "250m", "memory": "128Mi"},
                limits={"cpu": "1"},
            )
        ],
        tolerations=(Toleration(key="dedicated", operator="Exists"),),
        affinity=Affinity(
            node_affinity=NodeAffinity(
                required_during_scheduling_ignored_during_execution=NodeSelector(
                    node_selector_terms=(
                        NodeSelectorTerm(
                            match_expressions=(
                                NodeSelectorRequirement(
                                    "topology.kubernetes.io/zone",
                                    "In",
                                    ("zone-a",),
                                ),
                            )
                        ),
                    )
                )
            )
        ),
    )


def _samples():
    return [
        _node(),
        _pod(uid="default/p0"),
        Resource.from_map({"cpu": "100m", "memory": "64Mi"}),
        PodDisruptionBudget(
            name="pdb",
            selector=LabelSelector(match_labels={"app": "p0"}),
            disruptions_allowed=1,
        ),
    ]


def _wait(predicate, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return predicate()


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------


def test_every_kind_roundtrips_binary_equals_json():
    """Every registered kind's envelope survives frame→decode with the
    decoded value byte-identical (canonical JSON) to the JSON path, and
    api.codec.decode reconstructs an equal object from either."""
    assert set(KINDS) == {"Pod", "Node", "Resource", "PodDisruptionBudget"}
    for obj in _samples():
        env = encode(obj)
        via_binary = wire_codec.decode_frame(wire_codec.encode_frame(env))[0]
        via_json = json.loads(json.dumps(env))
        assert _canon(via_binary) == _canon(via_json) == _canon(env)
        assert decode(via_binary) == decode(via_json) == obj


def test_watch_event_and_list_envelopes_roundtrip():
    for etype in ("ADDED", "MODIFIED", "DELETED"):
        for obj in _samples():
            env = encode(obj)
            nested = wire_codec.encode_nested(env)
            frame = wire_codec.encode_event(etype, 7, nested)
            got, off = wire_codec.decode_frame(frame)
            assert off == len(frame)
            assert _canon(got) == _canon({"type": etype, "rv": 7, "object": env})
    nested = [wire_codec.encode_nested(encode(o)) for o in _samples()]
    lst, _ = wire_codec.decode_frame(wire_codec.encode_list_frame(42, nested))
    assert _canon(lst) == _canon(
        {"resourceVersion": 42, "items": [encode(o) for o in _samples()]}
    )


def test_nested_splice_shares_one_encoding():
    """The SAME nested blob spliced into an event frame and a list frame
    decodes identically in both — the encode-once/zero-copy contract."""
    env = encode(_pod(uid="default/share"))
    blob = wire_codec.encode_nested(env)
    evt, _ = wire_codec.decode_frame(wire_codec.encode_event("ADDED", 1, blob))
    lst, _ = wire_codec.decode_frame(wire_codec.encode_list_frame(1, [blob]))
    assert _canon(evt["object"]) == _canon(lst["items"][0]) == _canon(env)


def test_scalar_edge_values_roundtrip():
    value = {
        "big": 2**70,
        "neg": -(2**70),
        "zero": 0,
        "float": 3.141592653589793,
        "inf_free": 1e308,
        "none": None,
        "true": True,
        "false": False,
        "empty": "",
        "long": "x" * 5000,
        "uni": "schrödinger-猫",
        "list": [1, [2, [3, {"deep": "😀"}]], ""],
        "repeat": ["repeated-key"] * 8,  # dynamic-table hits
    }
    got = wire_codec.decode_frame(wire_codec.encode_frame(value))[0]
    assert got == value
    # trailing garbage is rejected, truncation reads as no frame
    frame = wire_codec.encode_frame(value)
    with pytest.raises(ValueError):
        wire_codec.decode_value(frame[4:] + b"\x00")
    import io

    assert wire_codec.read_frame(io.BytesIO(frame[: len(frame) // 2])) is None


def test_static_table_is_deterministic():
    """The static intern table is part of the wire contract between a
    server and its clients in one process generation — both sides build
    it from the same vocabulary, so it must be stable and collision-free."""
    assert len(set(wire_codec.STATIC_STRINGS)) == len(wire_codec.STATIC_STRINGS)
    for key in ("kind", "object", "type", "labels", "ADDED", "resourceVersion"):
        assert key in wire_codec.STATIC_STRINGS


# ---------------------------------------------------------------------------
# byte identity with the byte layer as it stood at fbf4939 (ISSUE 46)
#
# The PLAIN REFERENCE: the parent's `_write_varint` / `_read_varint` /
# `_Encoder` / `_decode` and the assemblers around them, copied verbatim (the
# names prefixed, nothing else) — a statement a byte, a tuple a value.  The
# module's fragments must write the bytes this writes and read the tree it
# reads, for every value below; the three hex literals further down pin the
# format itself, so that it cannot drift together with this copy.
# ---------------------------------------------------------------------------

_REF_STATIC_INDEX = {s: i for i, s in enumerate(wire_codec.STATIC_STRINGS)}
_REF_U32 = struct.Struct("!I")
_REF_F64 = struct.Struct("!d")


def _ref_write_varint(out, n):
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(bytes((b | 0x80,)))
        else:
            out.append(bytes((b,)))
            return


def _ref_read_varint(buf, pos):
    shift = 0
    n = 0
    while True:
        b = buf[pos]
        pos += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, pos
        shift += 7


def _ref_zigzag(n):
    return (n << 1) if n >= 0 else (-(n << 1) - 1)


def _ref_unzigzag(z):
    return (z >> 1) if not z & 1 else -((z + 1) >> 1)


class _RefEncoder:
    def __init__(self):
        self.out = []
        self.dynamic = {}

    def value(self, v):
        out = self.out
        if v is None:
            out.append(b"\x00")
        elif v is True:
            out.append(b"\x02")
        elif v is False:
            out.append(b"\x01")
        elif isinstance(v, int):
            out.append(b"\x03")
            _ref_write_varint(out, _ref_zigzag(v))
        elif isinstance(v, float):
            out.append(b"\x04")
            out.append(_REF_F64.pack(v))
        elif isinstance(v, str):
            self.string(v)
        elif isinstance(v, (list, tuple)):
            out.append(b"\x08")
            _ref_write_varint(out, len(v))
            for x in v:
                self.value(x)
        elif isinstance(v, dict):
            out.append(b"\x09")
            _ref_write_varint(out, len(v))
            for k, x in v.items():
                if not isinstance(k, str):
                    raise TypeError(f"wire_codec: non-str dict key {k!r}")
                self.string(k)
                self.value(x)
        else:
            raise TypeError(f"wire_codec: unsupported {type(v)!r}")

    def string(self, s):
        out = self.out
        idx = _REF_STATIC_INDEX.get(s)
        if idx is not None:
            out.append(b"\x06")
            _ref_write_varint(out, idx)
            return
        idx = self.dynamic.get(s)
        if idx is not None:
            out.append(b"\x07")
            _ref_write_varint(out, idx)
            return
        self.dynamic[s] = len(self.dynamic)
        raw = s.encode()
        out.append(b"\x05")
        _ref_write_varint(out, len(raw))
        out.append(raw)

    def splice(self, nested_blob):
        self.out.append(nested_blob)

    def body(self):
        return b"".join(self.out)


def _ref_encode_value(v):
    enc = _RefEncoder()
    enc.value(v)
    return enc.body()


def _ref_encode_nested(v):
    body = _ref_encode_value(v)
    out = [b"\x0a"]
    _ref_write_varint(out, len(body))
    out.append(body)
    return b"".join(out)


def _ref_encode_frame(v):
    body = _ref_encode_value(v)
    return _REF_U32.pack(len(body)) + body


def _ref_encode_event(etype, rv, nested_obj):
    enc = _RefEncoder()
    enc.out.append(b"\x09")
    _ref_write_varint(enc.out, 3 if nested_obj is not None else 2)
    enc.string("type")
    enc.string(etype)
    enc.string("rv")
    enc.value(rv)
    if nested_obj is not None:
        enc.string("object")
        enc.splice(nested_obj)
    body = enc.body()
    return _REF_U32.pack(len(body)) + body


def _ref_encode_list_frame(rv, nested_items):
    enc = _RefEncoder()
    enc.out.append(b"\x09")
    _ref_write_varint(enc.out, 2)
    enc.string("resourceVersion")
    enc.value(rv)
    enc.string("items")
    enc.out.append(b"\x08")
    _ref_write_varint(enc.out, len(nested_items))
    for blob in nested_items:
        enc.splice(blob)
    body = enc.body()
    return _REF_U32.pack(len(body)) + body


def _ref_decode(buf, pos, dynamic):
    tag = buf[pos]
    pos += 1
    if tag == 0x00:
        return None, pos
    if tag == 0x01:
        return False, pos
    if tag == 0x02:
        return True, pos
    if tag == 0x03:
        z, pos = _ref_read_varint(buf, pos)
        return _ref_unzigzag(z), pos
    if tag == 0x04:
        return _REF_F64.unpack_from(buf, pos)[0], pos + 8
    if tag == 0x05:
        n, pos = _ref_read_varint(buf, pos)
        s = buf[pos : pos + n].decode()
        dynamic.append(s)
        return s, pos + n
    if tag == 0x06:
        i, pos = _ref_read_varint(buf, pos)
        return wire_codec.STATIC_STRINGS[i], pos
    if tag == 0x07:
        i, pos = _ref_read_varint(buf, pos)
        return dynamic[i], pos
    if tag == 0x08:
        n, pos = _ref_read_varint(buf, pos)
        out = []
        for _ in range(n):
            v, pos = _ref_decode(buf, pos, dynamic)
            out.append(v)
        return out, pos
    if tag == 0x09:
        n, pos = _ref_read_varint(buf, pos)
        d = {}
        for _ in range(n):
            k, pos = _ref_decode(buf, pos, dynamic)
            v, pos = _ref_decode(buf, pos, dynamic)
            d[k] = v
        return d, pos
    if tag == 0x0A:
        n, pos = _ref_read_varint(buf, pos)
        v, _ = _ref_decode(buf, pos, [])  # fresh table: self-contained blob
        return v, pos + n
    raise ValueError(f"wire_codec: bad tag 0x{tag:02x} at {pos - 1}")


def _ref_decode_value(body):
    v, pos = _ref_decode(body, 0, [])
    if pos != len(body):
        raise ValueError(
            f"wire_codec: {len(body) - pos} trailing bytes after value"
        )
    return v


def _same_tree(a, b) -> bool:
    """Equal AND of the same types all the way down (``True`` is not ``1``,
    ``-0.0`` is not ``0.0``, ``nan`` is ``nan``): ``==`` alone hides all three."""
    return repr(a) == repr(b)


def _plain(v):
    """``v`` in the builtin types a decoder hands back (what ``json.loads``
    of its JSON would give, for a value JSON can carry)."""
    if isinstance(v, dict):
        return {str(k): _plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    for base in (bool, int, float, str):
        if isinstance(v, base):
            return base(v)
    return v


def _bench_pods():
    """One pod of every template the benchmark's configurations hold (plain,
    spread, preferred / required (anti-)affinity), pending and bound, built
    by the harness's own builder."""
    from benchmarks import workload
    from kubernetes_tpu.api import types as T

    seen, out = set(), []
    root = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs")
    for fname in sorted(os.listdir(root)):
        with open(os.path.join(root, fname)) as f:
            cfg = json.load(f)
        for template in sorted(cfg["pod_templates"]):
            key = json.dumps(cfg["pod_templates"][template], sort_keys=True)
            if key in seen:
                continue
            seen.add(key)
            spec = workload.pod_specs(cfg, template, 8, "measure", "sched-1")[7]
            for node in ("", "scheduler-perf-4242"):
                tag = f"{fname[:-5]}:{template}:{'bound' if node else 'pending'}"
                out.append((tag, encode(workload.build_pod(T, spec, node_name=node))))
    return out


def _journal_values():
    root = os.path.join(os.path.dirname(__file__), "fixtures", "journals")
    for fname in sorted(os.listdir(root)):
        if fname.endswith(".jsonl"):
            with open(os.path.join(root, fname)) as f:
                yield f"journal:{fname[:-6]}", [json.loads(line) for line in f]


_WORDS = tuple(wire_codec.STATIC_STRINGS[::7]) + (
    "", "a", "measure-7", "zone-a", "ünïcode", "猫", "x" * 130, "node-" + "y" * 40,
)


def _random_tree(rng, depth=0):
    roll = rng.random()
    if depth > 3 or roll < 0.45:
        return rng.choice((
            None, True, False, 0, 1, -1, rng.randrange(-300, 300),
            rng.randrange(-(2**70), 2**70), rng.random() * 1e6, -0.0,
            rng.choice(_WORDS), rng.choice(_WORDS) + str(rng.randrange(4)),
        ))
    if roll < 0.7:
        return [_random_tree(rng, depth + 1) for _ in range(rng.randrange(0, 6))]
    return {
        rng.choice(_WORDS) + rng.choice(("", "", str(rng.randrange(3)))):
            _random_tree(rng, depth + 1)
        for _ in range(rng.randrange(0, 7))
    }


def _identity_cases():
    cases = [(f"kind:{type(o).__name__}", encode(o)) for o in _samples()]
    cases += _bench_pods()
    cases += [
        ("bulk-bind-request", {"items": [
            {"uid": f"default/measure-{i}", "node": f"scheduler-perf-{i % 3}"}
            for i in range(1024)]}),
        ("bulk-bind-response", {"ok": False, "results": [
            None, {"code": 409, "error": "pod default/p1 is already bound", "node": "n1"},
            None, {"code": 404, "error": "pod 'default/gone' not found"}]}),
        ("bind-request", {"node": "n1", "uid": "default/p0", "idempotent": True}),
    ]
    cases += list(_journal_values())
    rng = random.Random(46)
    cases += [(f"random-{i}", _random_tree(rng)) for i in range(200)]
    return cases


_IDENTITY = _identity_cases()


@pytest.mark.parametrize("value", [c[1] for c in _IDENTITY], ids=[c[0] for c in _IDENTITY])
def test_bytes_and_tree_identical_to_the_parents_byte_layer(value):
    """The encoder gives the reference's bytes in every assembly (body,
    nested blob, frame, the 3-key and the 2-key event, a list frame), and
    the decoder the reference's tree from each of them."""
    body = _ref_encode_value(value)
    assert wire_codec.encode_value(value) == body
    for item in value if isinstance(value, list) else ():  # a journal's lines, each alone
        alone = _ref_encode_value(item)
        assert wire_codec.encode_value(item) == alone
        assert _same_tree(wire_codec.decode_value(alone), _ref_decode_value(alone))
    nested = _ref_encode_nested(value)
    assert wire_codec.encode_nested(value) == nested
    frame = _ref_encode_frame(value)
    assert wire_codec.encode_frame(value) == frame
    want = _ref_decode_value(body)
    assert _same_tree(wire_codec.decode_value(body), want)
    assert _same_tree(wire_codec.decode_frame(frame), (want, len(frame)))
    for etype, rv, blob in (
        ("MODIFIED", 123456, nested), ("ADDED", 0, nested), ("DELETED", 2**40, nested),
        ("BOOKMARK", 2**31 + 5, None), ("ERROR", 127, None),
    ):
        event = _ref_encode_event(etype, rv, blob)
        assert wire_codec.encode_event(etype, rv, blob) == event
        assert _same_tree(wire_codec.decode_frame(event)[0], _ref_decode_value(event[4:]))
        assert _same_tree(wire_codec.read_frame(io.BytesIO(event)), _ref_decode_value(event[4:]))
    for blobs in ([], [nested], [nested] * 200):
        lst = _ref_encode_list_frame(2**33, blobs)
        assert wire_codec.encode_list_frame(2**33, blobs) == lst
        assert _same_tree(wire_codec.decode_frame(lst)[0], _ref_decode_value(lst[4:]))


# Taken from the parent (fbf4939) with ITS module, not from the copy above.
_PINNED = {
    "bound-pod-default-MODIFIED": (
        "MODIFIED", 123456,
        lambda: _bench_pod_default("scheduler-perf-4242"),
        "0000007309030602060e06030380890f06010a6309020600062506010904061305096d"
        "6561737572652d37060b051164656661756c742f6d6561737572652d37062705137363"
        "686564756c65722d706572662d34323432062c080109020613050163062d0902066805"
        "043130306d061805053530304d69",
    ),
    "node-ADDED": (
        "ADDED", 7, lambda: encode(_node()),
        "000000b209030602060d0603030e06010aa30109020600061206010905061305026e30"
        "06140903066a0700066b05067a6f6e652d61050f637573746f6d2fcebb2d6c6162656c"
        "0509c3bc6ec3af636f646506160904061703807d061803808080808002061a03dc0106"
        "1b0901050d7470752e6465762f63686970730308061c0904061703807d061803808080"
        "808002061a03dc01061b090107040308061d08010902061e0509646564696361746564"
        "061f0503747075",
    ),
    "BOOKMARK": ("BOOKMARK", 2**31 + 5, None, "0000000e0902060206100603038a80808010"),
}


def _bench_pod_default(node_name):
    from benchmarks import workload
    from kubernetes_tpu.api import types as T

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs",
                           "sched-perf-basic-5k.json")) as f:
        spec = workload.group_specs(json.load(f), "measure_pods", "measure", 8)[7]
    return encode(workload.build_pod(T, spec, node_name=node_name))


@pytest.mark.parametrize("name", sorted(_PINNED))
def test_pinned_frames_of_the_parent(name):
    etype, rv, envelope, hexed = _PINNED[name]
    env = envelope() if envelope else None
    frame = wire_codec.encode_event(
        etype, rv, wire_codec.encode_nested(env) if envelope else None)
    assert frame.hex() == hexed
    want = {"type": etype, "rv": rv, **({"object": env} if envelope else {})}
    assert _same_tree(wire_codec.decode_frame(bytes.fromhex(hexed)), (want, len(frame)))
    assert _same_tree(_ref_decode_value(bytes.fromhex(hexed)[4:]), want)


# ---------------------------------------------------------------------------
# the edges: what is accepted round-trips as the reference reads it, what is
# refused is refused with the reference's exception
# ---------------------------------------------------------------------------


class _Str(str):
    pass


class _Dict(dict):
    pass


class _Float(float):
    pass


class _Level(enum.IntEnum):
    LOW = 1
    HIGH = 300


_Pair = collections.namedtuple("_Pair", "key value")

_EDGES = {
    "varint-boundaries": [n * s for n in (0, 1, 63, 64, 127, 128, 8191, 8192, 16383,
                                          16384, 2**31, 2**63, 2**63 + 1, 2**70)
                          for s in (1, -1)],
    "bool-and-int-kept-apart": [True, False, 1, 0, 1.0, 0.0, {"t": True, "one": 1}],
    "floats": [0.5, -0.0, 0.0, float("inf"), float("-inf"), float("nan"), 1e308, 5e-324],
    "string-lengths": ["", "x" * 127, "x" * 128, "x" * 200, "é" * 100, "x" * 16384],
    "non-ascii": {"schrödinger-猫": "😀", "ключ": ["значение", "значение"]},
    "dynamic-table-past-the-fragments": [f"s{i}" for i in range(300)] * 2,
    "dynamic-keys-past-the-fragments": [{f"k{i}": i for i in range(300)}] * 2,
    "long-list": list(range(2000)),
    "wide-dict": {f"k{i}": None for i in range(130)},
    "empty-containers": {"list": [], "dict": {}, "tuple": (), "nested": [[], {}, [{}]]},
    "static-strings-as-values": list(wire_codec.STATIC_STRINGS),
    "subclasses": [_Str("kind"), _Str("dyn"), _Str("dyn"), _Dict(a=1, kind=_Str("Pod")),
                   _Float(2.5), _Level.LOW, _Level.HIGH, _Pair("k", [1]),
                   collections.OrderedDict(z=1, a=2), {_Str("name"): 1, _Str("dyn"): 2}],
}


@pytest.mark.parametrize("name", sorted(_EDGES))
def test_edge_values_roundtrip_as_the_reference_reads_them(name):
    value = _EDGES[name]
    for encode_new, encode_ref in (
        (wire_codec.encode_value, _ref_encode_value),
        (wire_codec.encode_nested, _ref_encode_nested),
    ):
        raw = encode_ref(value)
        assert encode_new(value) == raw
        assert _same_tree(wire_codec.decode_value(raw), _ref_decode_value(raw))
    got = wire_codec.decode_frame(wire_codec.encode_frame(value))[0]
    assert _same_tree(got, _plain(value))
    # an event type the static table does not hold travels inline
    blob = wire_codec.encode_nested(value)
    for etype in ("RELIST", "ключ", "x" * 200):
        assert wire_codec.encode_event(etype, 9, blob) == _ref_encode_event(etype, 9, blob)
        assert wire_codec.encode_event(etype, 9, None) == _ref_encode_event(etype, 9, None)
        assert wire_codec.decode_frame(wire_codec.encode_event(etype, 9, None))[0] == {
            "type": etype, "rv": 9}


def _np_bool():
    import numpy as np

    return np.bool_(True)


_REFUSED_VALUES = {
    "non-str-key": lambda: {1: "a"},
    "none-key": lambda: {"ok": {None: 1}},
    "tuple-key": lambda: [{("a", "b"): 1}],
    "bytes": lambda: {"raw": b"bytes"},
    "set": lambda: [{"a"}],
    "object": lambda: object(),
    "complex": lambda: [1, 2j],
    "numpy-bool": _np_bool,  # not a bool: numpy's own type, as refused as ever
}


@pytest.mark.parametrize("name", sorted(_REFUSED_VALUES))
def test_unencodable_values_are_refused_as_the_reference_refuses_them(name):
    value = _REFUSED_VALUES[name]()
    with pytest.raises(TypeError) as want:
        _ref_encode_value(value)
    for encoder in (wire_codec.encode_value, wire_codec.encode_nested, wire_codec.encode_frame):
        with pytest.raises(TypeError) as got:
            encoder(value)
        assert str(got.value) == str(want.value)


def _bad_bodies():
    good = _ref_encode_value({"name": "measure-7", "rv": 300, "f": 1.5, "l": ["a", "a"]})
    event = _ref_encode_event("MODIFIED", 5, _ref_encode_nested({"kind": "Pod"}))[4:]
    out = {
        "trailing-byte": good + b"\x00",
        "trailing-value-after-event": event + b"\x06\x01",
        "bad-tag-first": b"\x0b",
        "bad-tag-inside": b"\x08\x02\x00\xff",
        "bad-tag-as-dict-value": b"\x09\x01\x06\x00\x7f",
        "empty": b"",
        "static-index-past-the-table": b"\x06\xff\x7f",
        "dynamic-index-past-the-table": b"\x08\x02\x05\x01a\x07\x05",
        "nested-does-not-share-the-outer-table": b"\x08\x02\x05\x01a\x0a\x02\x07\x00",
        "bad-utf8": b"\x05\x02\xff\xfe",
        "unhashable-key": b"\x09\x01\x08\x00\x00",
        "varint-cut": b"\x03\x80",
        "float-cut": b"\x04\x00\x00\x00",
    }
    for cut in range(1, len(good)):
        out[f"cut-at-{cut:02d}"] = good[:cut]
    for cut in range(1, len(event)):
        out[f"event-cut-at-{cut:02d}"] = event[:cut]
    return out


_BAD = _bad_bodies()


@pytest.mark.parametrize("name", sorted(_BAD))
def test_bad_bodies_fail_as_the_reference_fails(name):
    """Trailing bytes and a bad tag are ``ValueError`` with the reference's
    message; a body cut anywhere raises what the reference raises there (or,
    where the cut falls inside a string, reads the shorter string as the
    reference does); ``read_frame`` answers a cut FRAME with ``None``."""
    body = _BAD[name]
    try:
        want = _ref_decode_value(body)
    except Exception as exc:  # noqa: BLE001 — whatever the reference raises
        with pytest.raises(type(exc)) as got:
            wire_codec.decode_value(body)
        if isinstance(exc, ValueError) and not isinstance(exc, UnicodeDecodeError):
            assert str(got.value) == str(exc)
        if name.startswith(("trailing", "bad-tag")):
            assert type(exc) is ValueError
    else:
        assert _same_tree(wire_codec.decode_value(body), want)
    frame = _REF_U32.pack(len(body) + 1) + body  # one byte short of its header
    assert wire_codec.read_frame(io.BytesIO(frame)) is None
    assert wire_codec.read_frame(io.BytesIO(frame[:3])) is None
    assert wire_codec.read_frame(io.BytesIO(b"")) is None


def test_a_call_leaves_the_collector_no_cycle():
    """The encoder's and the decoder's closures name themselves; each call
    unbinds its own before it returns, or every frame would leave a cycle
    (the function, its cells, the frame's buffers) for the collector — on the
    chip that read as ``runtime.gc_s_per_kpod`` doubled (PERF.md §6 PR 46)."""
    env = encode(_pod(uid="default/p0"))
    gc.collect()
    gc.disable()
    try:
        for _ in range(50):
            frame = wire_codec.encode_event("MODIFIED", 5, wire_codec.encode_nested(env))
            wire_codec.decode_frame(frame)
            wire_codec.encode_list_frame(5, [wire_codec.encode_nested(env)])
            with pytest.raises(TypeError):
                wire_codec.encode_value({"a": {1}})
            with pytest.raises(ValueError):
                wire_codec.decode_value(b"\x08\x02\x00\x0b")
        assert gc.collect() == 0
    finally:
        gc.enable()


def test_fragments_are_the_varint_encoding_of_their_index():
    """Every precomputed fragment is byte for byte what the general path
    writes, and the static table's fragments follow STATIC_STRINGS."""
    def ref(tag, n):
        out = [tag]
        _ref_write_varint(out, n)
        return b"".join(out)

    for s, i in _REF_STATIC_INDEX.items():
        assert wire_codec._SREF[s] == ref(b"\x06", i)
    assert len(wire_codec._SREF) == len(wire_codec.STATIC_STRINGS)
    for table, tag in ((wire_codec._STR_HEAD, b"\x05"), (wire_codec._DREF, b"\x07"),
                       (wire_codec._LIST_HEAD, b"\x08"), (wire_codec._DICT_HEAD, b"\x09"),
                       (wire_codec._NESTED_HEAD, b"\x0a")):
        assert [table[n] for n in range(len(table))] == [ref(tag, n) for n in range(128)]
    for n in (0, 1, 127, 128, 300, 16383, 16384, 2**35, 2**63, 2**64 + 1):
        assert wire_codec._varint(n) == ref(b"", n)


# ---------------------------------------------------------------------------
# HTTP end-to-end: negotiation + decoded identity
# ---------------------------------------------------------------------------


def test_http_list_and_watch_identical_across_codecs():
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    endpoint = f"http://127.0.0.1:{server.port}"
    try:
        api.create_node(_node("wire-n0"))
        for i in range(3):
            api.create_pod(_pod(f"wire-{i}", uid=f"default/wire-{i}"))
        api.bind(Pod(name="wire-0", uid="default/wire-0"), "wire-n0")
        bc = ApiClient(endpoint, codec="binary")
        jc = ApiClient(endpoint, codec="json")
        for res in ("nodes", "pods"):
            assert _canon(bc.list(res)) == _canon(jc.list(res))
        # watch: same events, byte-identical decoded envelopes
        def take(client, res, n):
            out = []
            for evt in client.watch_stream(res, 0):
                if evt.get("type") != "BOOKMARK":
                    out.append(evt)
                if len(out) >= n:
                    return out
            return out

        assert _canon(take(bc, "pods", 4)) == _canon(take(jc, "pods", 4))
    finally:
        server.stop()


def test_json_stays_the_default_without_accept():
    """A client that never asks for binary (curl, the debug endpoints)
    gets JSON — content negotiation, not a flag day."""
    import urllib.request

    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    try:
        api.create_node(_node())
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/api/v1/nodes"
        ) as resp:
            assert "application/json" in resp.headers.get("Content-Type", "")
            json.loads(resp.read())  # parses as plain JSON
    finally:
        server.stop()


def test_binary_frames_are_smaller_on_the_wire():
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    endpoint = f"http://127.0.0.1:{server.port}"
    try:
        for i in range(16):
            api.create_pod(_pod(f"sz-{i}", uid=f"default/sz-{i}"))
        ApiClient(endpoint, codec="binary").list("pods")
        ApiClient(endpoint, codec="json").list("pods")

        def _noted():
            with server._wire_mu:
                return {("binary", "tx"), ("json", "tx")} <= set(
                    server.wire_bytes
                )

        assert _wait(_noted)
        with server._wire_mu:
            wire = dict(server.wire_bytes)
        assert 0 < wire[("binary", "tx")] < wire[("json", "tx")]
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# condition-variable wakeup (no 0.5s poll)
# ---------------------------------------------------------------------------


def test_watch_cache_since_wakes_on_append():
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    try:
        cache = server.caches["pods"]
        api.create_pod(_pod("w0", uid="default/w0"))
        rv0 = cache.rv
        woke = {}

        def waiter():
            t0 = time.monotonic()
            events = cache.since(rv0, timeout=10.0)
            woke["latency_s"] = time.monotonic() - woke["recorded_at"]
            woke["blocked_s"] = time.monotonic() - t0
            woke["events"] = events

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.3)  # the watcher is idle, parked on the condvar
        woke["recorded_at"] = time.monotonic()
        api.create_pod(_pod("w1", uid="default/w1"))
        t.join(timeout=5.0)
        assert not t.is_alive()
        assert [e.rv for e in woke["events"]] == [rv0 + 1]
        assert woke["blocked_s"] >= 0.3  # it genuinely waited...
        assert woke["latency_s"] < 0.2  # ...and woke on notify, not a poll
        # an idle wait with nothing appended times out to [] on schedule
        t0 = time.monotonic()
        assert cache.since(cache.rv, timeout=0.05) == []
        assert time.monotonic() - t0 < 1.0
    finally:
        server.stop()


def test_watch_fanout_hop_is_sub_poll_interval_over_http():
    """PR 16's watch_fanout hop (api_write → watch_delivery) measures the
    wakeup the condvar replaced: with the 0.5s poll gone it sits in the
    low milliseconds even for watchers that were idle when the write
    landed."""
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    source = RemoteClusterSource(f"http://127.0.0.1:{server.port}")
    sched = Scheduler()
    try:
        source.connect(sched)
        mon = sched.install_controlplane(api_server=server, source=source)
        source.start()
        assert source.wait_for_sync()
        client = ApiClient(f"http://127.0.0.1:{server.port}")
        client.create_node(_node("hop-n0"))
        for i in range(4):
            client.create_pod(_pod(f"hop-{i}", uid=f"default/hop-{i}"))
            time.sleep(0.15)  # idle gaps: each write finds a parked watcher
        assert _wait(lambda: len(sched.queue) >= 4)
        sched.schedule_pending()
        assert _wait(lambda: mon.snapshot()["done_chains"] >= 4)
        fanout = mon.hop_summary()["watch_fanout"]
        assert fanout["count"] >= 4
        assert fanout["p50_s"] < 0.25, (
            f"watch_fanout p50 {fanout['p50_s']:.3f}s — the condvar wakeup "
            "should deliver well under the old 0.5s poll interval"
        )
    finally:
        source.stop()
        server.stop()


# ---------------------------------------------------------------------------
# wire-byte accounting on scrape
# ---------------------------------------------------------------------------


def test_wire_bytes_counters_land_in_metrics():
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    sched = Scheduler()
    try:
        sched.install_controlplane(api_server=server)
        bc = ApiClient(f"http://127.0.0.1:{server.port}", codec="binary")
        jc = ApiClient(f"http://127.0.0.1:{server.port}", codec="json")
        bc.create_node(_node("m0"))
        bc.list("nodes")
        jc.list("nodes")
        # the handler notes tx bytes after writing the response — give
        # the accounting a beat before scraping
        def _noted():
            with server._wire_mu:
                return {("binary", "tx"), ("json", "tx")} <= set(
                    server.wire_bytes
                )

        assert _wait(_noted)
        text = sched.expose_metrics()
        assert "scheduler_tpu_wire_bytes_total" in text
        for codec in ("binary", "json"):
            line = next(
                ln
                for ln in text.splitlines()
                if ln.startswith("scheduler_tpu_wire_bytes_total")
                and f'codec="{codec}"' in ln
                and 'direction="tx"' in ln
            )
            assert float(line.rsplit(" ", 1)[1]) > 0
        # counters are cumulative across scrapes (delta sync, no resets)
        before = sched.expose_metrics()
        with server._wire_mu:
            tx0 = server.wire_bytes[("binary", "tx")]
        bc.list("nodes")
        assert _wait(lambda: server.wire_bytes[("binary", "tx")] > tx0)
        after = sched.expose_metrics()

        def tx(text_):
            return sum(
                float(ln.rsplit(" ", 1)[1])
                for ln in text_.splitlines()
                if ln.startswith("scheduler_tpu_wire_bytes_total")
                and 'codec="binary"' in ln
            )

        assert tx(after) > tx(before)
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# bind retry idempotence (kill-after-apply)
# ---------------------------------------------------------------------------


class _KillAfterApply(ApiClient):
    """First binding POST: let the server apply it, then kill the
    response on the way back — the transport shape of a retried write."""

    def __init__(self, endpoint, **kw):
        super().__init__(endpoint, **kw)
        self.kills_left = 1
        self.killed = 0

    def _conn(self, fresh=False):
        real = super()._conn(fresh=fresh)
        outer = self

        class Proxy:
            def request(self, method, path, body=None, headers=None):
                self._arm = "/binding" in path and outer.kills_left > 0
                real.request(method, path, body=body, headers=headers)

            def getresponse(self):
                resp = real.getresponse()
                if self._arm:
                    outer.kills_left -= 1
                    outer.killed += 1
                    resp.read()  # server finished: the apply happened
                    raise ConnectionResetError(
                        "injected: response lost after apply"
                    )
                return resp

        return Proxy()


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_bind_retry_after_lost_response_is_idempotent(codec):
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    endpoint = f"http://127.0.0.1:{server.port}"
    try:
        api.create_node(_node("bind-n0"))
        api.create_node(_node("bind-n1"))
        pod = _pod("bind-p0", uid="default/bind-p0")
        api.create_pod(pod)
        client = _KillAfterApply(endpoint, codec=codec)
        client.bind(pod, "bind-n0")  # must NOT raise: retry sees its own 409
        assert client.killed == 1
        assert api.bindings == {"default/bind-p0": "bind-n0"}
        # a REAL conflict — different node — still surfaces as 409
        with pytest.raises(ApiError) as ei:
            ApiClient(endpoint, codec=codec).bind(pod, "bind-n1")
        assert ei.value.code == 409
        assert api.bindings == {"default/bind-p0": "bind-n0"}
    finally:
        server.stop()


@pytest.mark.parametrize("codec", ["binary", "json"])
def test_bind_many_tolerates_conflict_on_retry(codec):
    api = FakeCluster(pv_controller=False)
    server = ApiServer(api).start()
    endpoint = f"http://127.0.0.1:{server.port}"
    try:
        api.create_node(_node("bm-n0"))
        api.create_node(_node("bm-n1"))
        p0 = _pod("bm-p0", uid="default/bm-p0")
        p1 = _pod("bm-p1", uid="default/bm-p1")
        api.create_pod(p0)
        api.create_pod(p1)
        client = ApiClient(endpoint, codec=codec)
        assert client.bind_many([(p0, "bm-n0")]) == [None]
        # replaying the same binding (lost-response retry) is a success;
        # a different node for an already-bound pod is a real error
        errs = client.bind_many([(p0, "bm-n0"), (p1, "bm-n1")])
        assert errs[0] is None and errs[1] is None
        errs = client.bind_many([(p0, "bm-n1")])
        assert errs[0] is not None and "409" in errs[0]
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# chaos over binary frames
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["watch-cut", "compaction"])
def test_chaos_watch_faults_over_binary_frames(name, tmp_path):
    """watch-cut and forced-410/compaction faults inject ABOVE the frame
    seam (on decoded events), so they pass the oracle riding binary
    frames, the recorded journal replays to identical placements
    (replay is codec-untouched), and the SAME scenario under the JSON
    codec converges too — drain batching is wall-clock dependent, so
    journal bytes are not compared across codecs."""
    import dataclasses

    from kubernetes_tpu.chaos.journal import replay
    from kubernetes_tpu.chaos.runner import SCENARIOS, run_scenario

    scn = SCENARIOS[name]
    assert scn.mode == "http" and scn.codec == "binary"
    for codec in ("binary", "json"):
        path = str(tmp_path / f"{name}-{codec}.jsonl")
        res = run_scenario(dataclasses.replace(scn, codec=codec), path)
        assert res.problems == [], f"{name}/{codec} oracle: {res.problems}"
        assert res.injected, f"{name}/{codec} injected no faults"
        rr = replay(path)
        assert rr.ok, f"{name}/{codec} replay: {rr.mismatches[:2]}"


@pytest.mark.slow
def test_wire_soak_chaos_enabled_with_hollow_nodes():
    """Tier-1-sized config17 soak shape: control-plane + device faults
    simultaneously, binary frames end to end, a hollow-node fleet riding
    the same apiserver — the post-run invariant oracle must be clean."""
    from kubernetes_tpu.chaos.runner import run_chaos_soak

    out = run_chaos_soak(
        n_nodes=6,
        n_pods=48,
        rounds=2,
        fault_rate=0.1,
        device_fault_rate=0.1,
        codec="binary",
        hollow_nodes=4,
    )
    assert out["problems"] == []
    assert out["bound"] == 48
    assert out["codec"] == "binary" and out["hollow_nodes"] == 4
    assert out["injected_total"] > 0
