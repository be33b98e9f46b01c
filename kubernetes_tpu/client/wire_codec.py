"""Binary wire codec for the list/watch/bind hot path.

The reference leans on protobuf precisely because JSON list/watch
dominates at scale (SURVEY L0-L4); this is the same move shrunk to the
repo's JSON-safe value domain.  One self-describing, length-prefixed
FRAME carries any structure the JSON tier carries, and decodes to the
IDENTICAL Python structure ``json.loads`` would have produced — so every
parity, journal-replay, and relist guarantee carries over unchanged and
a decoded object is byte-identical between codecs (same ``json.dumps``).

Frame layout (all integers big-endian):

    frame   := u32 body-length | body
    body    := value
    value   := 0x00                          # None
             | 0x01 | 0x02                   # False | True
             | 0x03 zigzag-varint            # int (unbounded)
             | 0x04 f64                      # float (8-byte IEEE double)
             | 0x05 varint utf8-bytes        # str, inline (registers in the
                                             #   frame's dynamic table)
             | 0x06 varint                   # str, STATIC table ref
             | 0x07 varint                   # str, dynamic table ref
             | 0x08 varint value*            # list  (count, then items)
             | 0x09 varint (value value)*    # dict  (count, then k/v pairs;
                                             #   keys are str values)
             | 0x0A varint body              # NESTED value: byte length +
                                             #   a self-contained body with
                                             #   its OWN dynamic table

STRING INTERNING is two-tier.  The STATIC table is baked into this
module — every dataclass field name reachable from the codec's KINDS
(the wire keys), the envelope/protocol keys, event types, and the common
label/taint vocabulary — so the strings that dominate Node/Pod payloads
cost one tag + one varint.  Anything else goes inline once per frame and
by dynamic back-reference after that (repeated label values, node names
in taint messages).  Both sides derive the static table from the same
``_build_static_table()``, so there is no negotiation of table versions:
the table is part of the content type.

The NESTED value (0x0A) is the ZERO-COPY seam: an object envelope is
encoded ONCE into a nested blob at watch-cache append time, and that
same blob is spliced verbatim into every watch event frame and every
binary list response (cacher.go keeps one encoded object per event for
the same reason).  A nested body carries its own dynamic table, so
splicing can never desynchronize the enclosing frame's table.

Content negotiation: clients send ``Accept``/``Content-Type`` of
``CT_BINARY``; the server answers in kind and keeps JSON the default for
anything that doesn't ask (curl debugging, the chaos journal's decoded
entries, old clients).  When JSON still wins: see WIRE.md.
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Any, Dict, List, Optional, Tuple, get_args, get_type_hints

CT_JSON = "application/json"
CT_BINARY = "application/vnd.ktpu.wire+binary"

_U32 = struct.Struct("!I")
_F64 = struct.Struct("!d")

# Lock-discipline registry (kubernetes_tpu.analysis): the codec is PURE —
# the static table and the fragment tables below are built once at import
# and never mutated, and every encode/decode call carries its state in the
# locals of that one call.  Registered empty so the checker vets any mutable
# state a future change introduces here (encoders ride apiserver handler
# threads, reflector threads, and the watch-cache append path concurrently).
# Plain assignment — analysis.core.module_literal reads ast.Assign only.
_KTPU_GUARDED = {}


# ---------------------------------------------------------------------------
# static intern table
# ---------------------------------------------------------------------------

# envelope / protocol keys and values the server's frames always carry
_PROTOCOL_STRINGS = (
    "kind",
    "object",
    "type",
    "rv",
    "items",
    "resourceVersion",
    "results",
    "error",
    "code",
    "ok",
    "node",
    "uid",
    "idempotent",
    "ADDED",
    "MODIFIED",
    "DELETED",
    "BOOKMARK",
    "ERROR",
)

# common label / taint / value vocabulary (the reference's well-known
# keys) — frames carrying them pay a ref, not the full string
_COMMON_STRINGS = (
    "app",
    "cpu",
    "memory",
    "pods",
    "kubernetes.io/hostname",
    "topology.kubernetes.io/zone",
    "topology.kubernetes.io/region",
    "node.kubernetes.io/not-ready",
    "node.kubernetes.io/unreachable",
    "node.kubernetes.io/unschedulable",
    "NoSchedule",
    "PreferNoSchedule",
    "NoExecute",
    "Exists",
    "Equal",
    "In",
    "NotIn",
    "DoNotSchedule",
    "ScheduleAnyway",
    "Honor",
    "Ignore",
    "TCP",
    "UDP",
    "Pending",
    "Running",
    "Always",
    "Never",
    "PreemptLowerPriority",
    "default",
    "default-scheduler",
)


def _collect_field_names(cls, seen: set, out: List[str]) -> None:
    """Every dataclass field name reachable from ``cls`` (the wire keys
    ``api.codec.to_wire`` emits), depth-first in declaration order —
    deterministic, so server and client derive the same table."""
    if not dataclasses.is_dataclass(cls) or cls in seen:
        return
    seen.add(cls)
    try:
        hints = get_type_hints(cls)
    except Exception:  # noqa: BLE001 — unresolvable forward ref: skip nest
        hints = {}
    for f in dataclasses.fields(cls):
        if f.name not in out:
            out.append(f.name)
        _walk_hint(hints.get(f.name), seen, out)


def _walk_hint(hint, seen: set, out: List[str]) -> None:
    if hint is None:
        return
    if dataclasses.is_dataclass(hint):
        _collect_field_names(hint, seen, out)
        return
    for a in get_args(hint):
        _walk_hint(a, seen, out)


def _build_static_table() -> Tuple[str, ...]:
    from kubernetes_tpu.api.codec import KINDS

    out: List[str] = list(_PROTOCOL_STRINGS)
    seen: set = set()
    for kind in sorted(KINDS):
        if kind not in out:
            out.append(kind)
        _collect_field_names(KINDS[kind], seen, out)
    for s in _COMMON_STRINGS:
        if s not in out:
            out.append(s)
    return tuple(out)


STATIC_STRINGS: Tuple[str, ...] = _build_static_table()


# ---------------------------------------------------------------------------
# primitives and fragments
# ---------------------------------------------------------------------------


def _varint(n: int) -> bytes:
    """LEB128 of ``n`` — the general path, for what no fragment table holds
    (an rv, a large int, a length or an index past one byte)."""
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _heads(tag: int) -> Tuple[bytes, ...]:
    """``tag`` + the one-byte varint of every n below 128, as one ``bytes``."""
    return tuple(bytes((tag, n)) for n in range(0x80))


# FRAGMENTS: what is constant on the wire, built once at import from the same
# STATIC_STRINGS both sides derive — looked up where the codec used to compute
# them a byte a statement.  They ARE the frame format: every fragment is byte
# for byte what ``tag + _varint(n)`` gives (pinned in tests/test_wire_codec.py).
_STR_HEAD, _DREF, _LIST_HEAD, _DICT_HEAD, _NESTED_HEAD = (
    _heads(tag) for tag in (0x05, 0x07, 0x08, 0x09, 0x0A)
)
_SREF: Dict[str, bytes] = {
    s: b"\x06" + _varint(i) for i, s in enumerate(STATIC_STRINGS)
}


def _head(table: Tuple[bytes, ...], n: int) -> bytes:
    """``table``'s tag + varint(n), for any n (the encoder's hot loops spell
    this out with their tag's literal instead of calling)."""
    return table[n] if n < 0x80 else table[0][:1] + _varint(n)


def _int(n: int) -> bytes:
    return b"\x03" + _varint((n << 1) if n >= 0 else (-(n << 1) - 1))  # zigzag


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

# a subclass is encoded as the first of these it is an instance of: the
# order of the isinstance chain this dispatch replaced
_BASES = (int, float, str, list, tuple, dict)


def encode_value(v: Any) -> bytes:
    """Value → frame BODY bytes (no length prefix).  One call is one
    frame's encoding context: the dynamic string table is per-frame (a
    nested blob carries its own)."""
    out: List[bytes] = []
    append = out.append
    dynamic: Dict[str, int] = {}
    sref = _SREF.get

    def string(s: str) -> None:
        frag = sref(s)
        if frag is None:
            idx = dynamic.get(s)
            if idx is None:
                dynamic[s] = len(dynamic)
                raw = s.encode()
                n = len(raw)
                append(_STR_HEAD[n] if n < 0x80 else b"\x05" + _varint(n))
                append(raw)
                return
            frag = _DREF[idx] if idx < 0x80 else b"\x07" + _varint(idx)
        append(frag)

    def value(v: Any, t: type) -> None:
        # t: type(v), or the base of _BASES a subclass is encoded as
        if t is str:
            string(v)
        elif t is dict:
            n = len(v)
            append(_DICT_HEAD[n] if n < 0x80 else b"\x09" + _varint(n))
            for k, x in v.items():
                frag = sref(k) if type(k) is str else None
                if frag is not None:
                    append(frag)  # a static key: its fragment, no call
                elif isinstance(k, str):
                    string(k)
                else:
                    raise TypeError(f"wire_codec: non-str dict key {k!r}")
                t = type(x)
                if t is str:
                    string(x)
                else:
                    value(x, t)
        elif t is list or t is tuple:
            n = len(v)
            append(_LIST_HEAD[n] if n < 0x80 else b"\x08" + _varint(n))
            for x in v:
                value(x, type(x))
        elif t is int:
            append(_int(v))
        elif v is None:
            append(b"\x00")
        elif t is bool:
            append(b"\x02" if v else b"\x01")
        elif t is float:
            append(b"\x04")
            append(_F64.pack(v))
        else:
            for base in _BASES:
                if isinstance(v, base):
                    return value(v, base)
            raise TypeError(f"wire_codec: unsupported {type(v)!r}")

    try:
        value(v, type(v))
    finally:
        value = None  # the closure names itself: leave the collector no cycle
    return b"".join(out)


def encode_nested(v: Any) -> bytes:
    """Value → a NESTED blob: the event and list assemblers below splice
    it where a value is expected — the zero-copy path: the blob's own
    dynamic table means no re-encode and no table interaction."""
    body = encode_value(v)
    return _head(_NESTED_HEAD, len(body)) + body


def encode_frame(v: Any) -> bytes:
    """Value → full length-prefixed frame."""
    body = encode_value(v)
    return _U32.pack(len(body)) + body


def _event_heads(etype: str) -> Tuple[bytes, bytes]:
    """An event body up to its rv, in the 2-key and the 3-key form."""
    rest = _SREF["type"] + encode_value(etype) + _SREF["rv"]
    return b"\x09\x02" + rest, b"\x09\x03" + rest


_EVENT_HEADS: Dict[str, Tuple[bytes, bytes]] = {
    s: _event_heads(s) for s in STATIC_STRINGS
}
_OBJECT_KEY = _SREF["object"]
_LIST_FRAME_HEAD = b"\x09\x02" + _SREF["resourceVersion"]


def encode_event(etype: str, rv: int, nested_obj: Optional[bytes]) -> bytes:
    """One watch event as a full frame:
    ``{"type": etype, "rv": rv, "object": <spliced blob>}`` — the blob is
    the object envelope encoded ONCE at watch-cache append time and
    shared across every watcher's stream and the binary list path."""
    short, long = _EVENT_HEADS.get(etype) or _event_heads(etype)
    if nested_obj is None:
        body = short + _int(rv)
    else:
        body = b"".join((long, _int(rv), _OBJECT_KEY, nested_obj))
    return _U32.pack(len(body)) + body


def encode_list_frame(rv: int, nested_items: List[bytes]) -> bytes:
    """A binary list response as one full frame:
    ``{"resourceVersion": rv, "items": [<spliced blobs>]}`` — items are
    the per-object blobs maintained by the watch cache, NOT re-encoded
    per request (the JSON list path re-serializes the full object set on
    every call; this path just concatenates)."""
    body = b"".join(
        (_LIST_FRAME_HEAD, _int(rv), _SREF["items"],
         _head(_LIST_HEAD, len(nested_items)), *nested_items)
    )
    return _U32.pack(len(body)) + body


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------


def _varint_at(buf: bytes, pos: int) -> Tuple[int, int]:
    """The general varint read — past one byte, which is read inline."""
    shift = n = 0
    while True:
        b = buf[pos]
        pos += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, pos
        shift += 7


def decode_value(body: bytes) -> Any:
    """Frame BODY bytes → value (the exact structure ``json.loads`` of
    the JSON encoding would produce).  ``pos`` and the dynamic table live
    in this call's closure: no ``(value, pos)`` tuple a value."""
    pos = 0
    dynamic: List[str] = []

    def value() -> Any:
        nonlocal pos, dynamic
        tag = body[pos]
        pos += 1
        if tag == 0x04:
            pos += 8
            return _F64.unpack_from(body, pos - 8)[0]
        if tag > 0x0A or tag < 0x03:
            if tag > 0x02:
                raise ValueError(f"wire_codec: bad tag 0x{tag:02x} at {pos - 1}")
            return (None, False, True)[tag]
        n = body[pos]  # every other tag carries a varint: one byte, mostly
        if n < 0x80:
            pos += 1
        else:
            n, pos = _varint_at(body, pos)
        if tag == 0x09:
            d = {}
            p = pos
            for _ in range(n):
                # a static key and a static or short inline string — nearly
                # every pair of the traffic — are read here, without a call
                if body[p] == 0x06 and (m := body[p + 1]) < 0x80:
                    k = STATIC_STRINGS[m]
                    p += 2
                else:
                    pos = p
                    k = value()
                    p = pos
                tag = body[p]
                if tag == 0x05 and (m := body[p + 1]) < 0x80:
                    p += 2 + m
                    d[k] = s = body[p - m : p].decode()
                    dynamic.append(s)
                elif tag == 0x06 and (m := body[p + 1]) < 0x80:
                    d[k] = STATIC_STRINGS[m]
                    p += 2
                else:
                    pos = p
                    d[k] = value()
                    p = pos
            pos = p
            return d
        if tag == 0x05:
            s = body[pos : pos + n].decode()
            pos += n
            dynamic.append(s)
            return s
        if tag == 0x06:
            return STATIC_STRINGS[n]
        if tag == 0x07:
            return dynamic[n]
        if tag == 0x08:
            return [value() for _ in range(n)]
        if tag == 0x03:
            return (n >> 1) if not n & 1 else -((n + 1) >> 1)  # zigzag
        end = pos + n  # NESTED: a self-contained body, its own fresh table
        outer, dynamic = dynamic, []
        v = value()
        dynamic = outer
        pos = end
        return v

    try:
        v = value()
    finally:
        value = None  # the closure names itself: leave the collector no cycle
    if pos != len(body):
        raise ValueError(
            f"wire_codec: {len(body) - pos} trailing bytes after value"
        )
    return v


def decode_frame(buf: bytes, offset: int = 0) -> Tuple[Any, int]:
    """One length-prefixed frame at ``offset`` → (value, next offset)."""
    (n,) = _U32.unpack_from(buf, offset)
    start = offset + 4
    return decode_value(buf[start : start + n]), start + n


def read_frame(stream) -> Optional[Any]:
    """Read one frame from a file-like stream (a dechunked HTTP response
    body).  Returns None on clean EOF — and on a connection cut mid-frame
    (truncated read), which the reflector handles exactly like a clean
    stream end: re-watch/relist from its current rv."""
    header = _read_exact(stream, 4)
    if header is None:
        return None
    (n,) = _U32.unpack(header)
    body = _read_exact(stream, n)
    if body is None:
        return None
    return decode_value(body)


def _read_exact(stream, n: int) -> Optional[bytes]:
    chunks: List[bytes] = []
    got = 0
    while got < n:
        chunk = stream.read(n - got)
        if not chunk:
            return None
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)
