"""HTTP API server: list + watch over a FakeCluster store.

The serving half of the reference's storage stack, shrunk to the
scheduler-relevant surface:

  * per-resource WATCH CACHE — a sliding window of (rv, type, object)
    events (apiserver/pkg/storage/cacher: watch_cache.go's rolling window)
    so watchers resume from a resourceVersion without hitting the store;
    a request older than the window gets 410 Gone, triggering the
    client's relist (reflector.go:340);
  * GET  /api/v1/{nodes,pods}                  → {"resourceVersion", "items"}
  * GET  /api/v1/{res}?watch=1&resourceVersion=N → chunked JSON-lines stream
  * POST /api/v1/{nodes,pods}                  → create (bare object, or
    {"items": [...]} for a bulk create in one request)
  * PUT  /api/v1/nodes/{name}                  → update
  * DELETE /api/v1/{res}/{key}                 → delete
  * POST /api/v1/pods/{uid}/binding            → the binding subresource
    (registry/core/pod/storage/storage.go:169 assignPod)
  * POST /api/v1/bindings                      → BULK bindings ({"items":
    [{"uid","node"}]} → per-item results) — the batch-first extension of
    the per-pod subresource
  * PATCH /api/v1/pods/{uid}/status            → nominatedNodeName patches

Writes go through the wrapped FakeCluster so its watch fan-out, PV
controller, and binding semantics stay authoritative; this server records
the fan-out into the watch cache and serves it over the wire.

WIRE FORMAT is content-negotiated (see client/wire_codec.py + WIRE.md):
JSON is the default — a request carrying ``Accept:`` /
``Content-Type: application/vnd.ktpu.wire+binary`` rides the binary
codec instead, where every watch event is encoded ONCE at append time
and the same bytes are shared by every watcher and the list path.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Deque, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, unquote, urlparse

from kubernetes_tpu.api.codec import decode, encode
from kubernetes_tpu.api.types import Node, Pod
from kubernetes_tpu.client import wire_codec
from kubernetes_tpu.metrics import annotation

# Events kept per resource.  Upstream's watch cache is not a fixed ring: it
# doubles, up to ``defaultUpperBoundCapacity`` = 100 * 1024 events, while its
# oldest event is younger than ``eventFreshDuration`` (75 s) — under a burst
# it holds the burst (watch_cache.go ``resizeCacheLocked``).  A drain's binds
# ARE such a burst (thousands of MODIFIED events a second while the reflector
# shares the interpreter with the loop): at 4,096 a watcher a second behind
# got 410 and re-LISTed every pod of the cluster in the middle of the drain
# (10 s of decoding at 100,000 pods), so the window is the bound upstream's
# cache grows to.
WATCH_WINDOW = 100 * 1024

# idle-watcher bookmark cadence: how long a stream sleeps ON THE CONDITION
# VARIABLE before emitting a progress BOOKMARK.  Event delivery never
# waits on this — record() notifies and the watcher wakes in microseconds;
# the interval only bounds how stale a quiet stream's rv report gets.
BOOKMARK_INTERVAL_S = 0.5


class _Event:
    """One recorded watch event.

    The BINARY frame is encoded ONCE at append time — every binary
    watcher of every stream writes the same bytes, and the nested object
    blob inside it is ALSO what the binary list path splices, so neither
    fanout nor list ever re-serializes (cacher.go keeps one encoded
    object per event the same way).  The JSON line is memoized lazily on
    first use: JSON is the debug default, not the hot path, so idle
    debug-format cost is zero.  The legacy ``(rv, line)`` tuple shape is
    preserved for existing callers that unpack or index."""

    __slots__ = ("rv", "etype", "envelope", "frame", "_line")

    def __init__(self, rv: int, etype: str, envelope: dict, frame: bytes):
        self.rv = rv
        self.etype = etype
        self.envelope = envelope
        self.frame = frame  # full binary event frame (shared, immutable)
        self._line: Optional[bytes] = None

    @property
    def json_line(self) -> bytes:
        line = self._line
        if line is None:
            # benign race: two threads may both serialize; same value,
            # single-store publish under the GIL
            line = self._line = (
                json.dumps(
                    {"type": self.etype, "rv": self.rv, "object": self.envelope}
                )
                + "\n"
            ).encode()
        return line

    def __iter__(self):
        return iter((self.rv, self.json_line))

    def __getitem__(self, i):
        return (self.rv, self.json_line)[i]


class _WatchCache:
    """Sliding window of events with condition-variable wakeup.

    Each event carries its WIRE BYTES, serialized once at record time
    (see ``_Event``); ``obj_frames`` keeps the latest nested object blob
    per store key so binary list responses splice instead of re-encoding
    the full object set per request."""

    def __init__(self, window: int = WATCH_WINDOW):
        self.events: Deque[_Event] = deque(maxlen=window)
        self.rv = 0
        self.cond = threading.Condition()
        # latest nested binary blob per object key (the encode-once side
        # of the binary LIST path), maintained under the cond in record()
        self.obj_frames: Dict[str, bytes] = {}
        # observability counters (controlplane tier scrapes deltas):
        # compactions that dropped events, and 410s served — always-on
        # plain ints under the cond, like rv
        self.compactions = 0
        self.gone_total = 0
        # active watcher registry: watcher id → last rv delivered to that
        # stream.  Registration/removal under the cond; the per-iteration
        # position update is a plain dict store (GIL-atomic) so the watch
        # loop never takes the lock just to report progress.
        self.watchers: Dict[int, int] = {}
        self._watcher_seq = 0

    def record(self, event_type: str, envelope: dict, key: Optional[str] = None) -> int:
        return self.record_many(event_type, [(envelope, key)])

    def record_many(self, event_type: str, entries) -> int:
        """Append ``entries`` ((envelope, key), ...) as ONE transaction:
        one acquisition of the cond, consecutive rvs in entry order, each
        event's frame and nested blob encoded once, ONE wake-up for the
        lot.  Returns the last rv (the entries hold rv-len+1 .. rv)."""
        deleted = event_type == "DELETED"
        with self.cond:
            rv = self.rv
            events, frames = self.events, self.obj_frames
            for envelope, key in entries:
                rv += 1
                nested = wire_codec.encode_nested(envelope)
                frame = wire_codec.encode_event(event_type, rv, nested)
                events.append(_Event(rv, event_type, envelope, frame))
                if key is not None:
                    if deleted:
                        frames.pop(key, None)
                    else:
                        frames[key] = nested
            self.rv = rv
            self.cond.notify_all()
            return rv

    def _stale(self, rv: int) -> bool:
        """rv precedes the retained window → the watcher must relist.

        With a NON-EMPTY window the oldest replayable position is
        events[0].rv - 1.  With an EMPTY window (deque wrap at maxlen 0
        during tests, explicit compaction, server restart) NOTHING is
        replayable, so any rv behind the head counter is stale — returning
        [] there would silently strand a watcher that can never catch up.
        """
        if self.events:
            return rv < self.events[0].rv - 1
        return rv < self.rv

    def since(self, rv: int, timeout: float) -> Optional[List[_Event]]:
        """Events with rv' > rv; None ⇒ rv fell out of the window (410).

        Blocks on the condition variable until an event lands (record()
        notifies — an idle watcher adds microseconds of delivery latency,
        not a poll interval) or ``timeout`` elapses ([] ⇒ still idle; the
        caller emits a BOOKMARK).  The wait loops against spurious
        wakeups and concurrent consumers racing for the same notify."""
        deadline = time.monotonic() + timeout
        with self.cond:
            while True:
                if self._stale(rv):
                    self.gone_total += 1
                    return None  # compacted away → 410 Gone
                out = self._tail(rv)
                if out:
                    return out
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return []
                self.cond.wait(remaining)

    def _tail(self, rv: int) -> List[_Event]:
        """The retained events with rv' > rv, oldest first.  Events are
        appended in rv order, so the walk starts at the head and stops at
        the first one the watcher has: a wake-up costs the new events,
        not the window."""
        out: List[_Event] = []
        for e in reversed(self.events):
            if e.rv <= rv:
                break
            out.append(e)
        out.reverse()
        return out

    def compact(self, keep: int = 0) -> None:
        """Drop all but the last ``keep`` retained events (the etcd
        compaction shape, on demand — the chaos runner's forced-410 lever).
        Wakes blocked watchers so stale ones see the 410 immediately."""
        with self.cond:
            if len(self.events) > keep:
                self.compactions += 1
            while len(self.events) > keep:
                self.events.popleft()
            self.cond.notify_all()


class ApiServer:
    def __init__(self, api, host: str = "127.0.0.1", port: int = 0):
        self.api = api
        self._mu = threading.Lock()
        # optional ControlPlaneMonitor (observability/controlplane.py),
        # set by monitor.attach_api_server: api-write breadcrumbs +
        # per-request accounting.  Every producer site gates on one
        # attribute read, so the unwired server pays a load + branch.
        self.cp = None
        self.caches: Dict[str, _WatchCache] = {
            "nodes": _WatchCache(),
            "pods": _WatchCache(),
        }
        # wire-byte accounting: (codec, direction) → total bytes, from the
        # server's perspective (tx = responses/streams, rx = request
        # bodies).  Plain dict under a dedicated mutex — handler threads
        # increment, the controlplane monitor scrapes deltas into
        # scheduler_tpu_wire_bytes_total at scrape time.
        self.wire_bytes: Dict[Tuple[str, str], int] = {}
        self._wire_mu = threading.Lock()
        # bulk binding transactions (POST /bindings → bind_txn): slices
        # applied and items in them.  Plain ints written under _mu, like
        # the watch caches' compactions.
        self.bulk_bind_txns = 0
        self.bulk_bind_items = 0
        # subscribe to the store's fan-out so every mutation (from any
        # client, or in-proc drivers) lands in the watch caches
        api.watch_nodes(
            lambda n: self._record("nodes", "ADDED", n),
            lambda old, new: self._record("nodes", "MODIFIED", new),
            lambda n: self._record("nodes", "DELETED", n),
        )
        api.watch_pods(
            lambda p: self._record("pods", "ADDED", p),
            lambda old, new: self._record("pods", "MODIFIED", new),
            lambda p: self._record("pods", "DELETED", p),
            # a bind_many slice: the stored pods, borrowed — serialised
            # into the watch cache before the call returns, nothing kept
            lambda pods: self._record_many("pods", "MODIFIED", pods),
        )
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # Nagle + the peer's delayed ACK turns every multi-write
            # response into a ~40ms stall on keep-alive connections —
            # fatal for per-pod request rates (kube-apiserver serves
            # HTTP/2 where this never applies).  StreamRequestHandler
            # applies this to the connection socket.
            disable_nagle_algorithm = True

            def log_message(self, fmt, *args):  # noqa: D401 — quiet
                pass

            # per-request accounting context, set by _begin at the top of
            # each verb handler and consumed by _json at response time
            _acct = None
            # the request's profiler span ``ktpu.apiserver.<verb>.<resource>``
            # (metrics.annotation): opened by _begin, closed at response
            # time on the same handler thread; nothing unless a profiler
            # session is live
            _ann = None

            def _begin(self, verb: str) -> None:
                parts = [
                    p for p in urlparse(self.path).path.split("/") if p
                ]
                res = parts[2] if len(parts) >= 3 and parts[0] == "api" else (
                    parts[0] if parts else "other"
                )
                self._end_span()  # a request that never answered
                self._ann = annotation(f"apiserver.{verb}.{res}").begin()
                cp = server.cp
                if cp is None or not cp.enabled:
                    self._acct = None
                    return
                self._acct = (cp, verb, res, time.monotonic())

            def _end_span(self) -> None:
                ann = self._ann
                if ann is not None:
                    self._ann = None
                    ann.end()

            # ----- content negotiation (Accept / Content-Type) ---------
            # JSON stays the DEBUG DEFAULT: a request that doesn't ask for
            # the binary content type gets exactly the old JSON wire, so
            # curl sessions, old clients, and the chaos journal's decoded
            # entries are untouched.

            def _wants_binary(self) -> bool:
                return wire_codec.CT_BINARY in (self.headers.get("Accept") or "")

            def _read_body(self):
                """Request body → value, negotiated via Content-Type."""
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length) if length else b""
                ct = self.headers.get("Content-Type") or ""
                if wire_codec.CT_BINARY in ct:
                    server._note_wire("binary", "rx", len(raw))
                    if not raw:
                        return {}
                    return wire_codec.decode_frame(raw)[0]
                server._note_wire("json", "rx", len(raw))
                return json.loads(raw or b"{}")

            def _send_raw(self, code: int, body: bytes, ctype: str, codec: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                server._note_wire(codec, "tx", len(body))
                acct = self._acct
                if acct is not None:
                    self._acct = None
                    cp, verb, res, t0 = acct
                    cp.note_request(verb, res, code, time.monotonic() - t0)
                self._end_span()

            def _json(self, code: int, payload) -> None:
                """Negotiated response: named for the historical default —
                answers in binary when the request's Accept asks for it."""
                if self._wants_binary():
                    return self._send_raw(
                        code,
                        wire_codec.encode_frame(payload),
                        wire_codec.CT_BINARY,
                        "binary",
                    )
                return self._send_raw(
                    code, json.dumps(payload).encode(), "application/json", "json"
                )

            def do_GET(self):  # noqa: N802
                self._begin("GET")
                u = urlparse(self.path)
                parts = [p for p in u.path.split("/") if p]
                q = parse_qs(u.query)
                if len(parts) == 4 and parts[:3] == ["api", "v1", "leases"]:
                    from kubernetes_tpu.util.leases import lease_to_wire

                    rec = server.api.lease_store.get(unquote(parts[3]))
                    if rec is None:
                        return self._json(404, {"error": "lease not found"})
                    return self._json(200, lease_to_wire(rec))
                if len(parts) == 3 and parts[:2] == ["api", "v1"]:
                    res = parts[2]
                    if res not in server.caches:
                        return self._json(404, {"error": "unknown resource"})
                    if q.get("watch", ["0"])[0] in ("1", "true"):
                        return self._watch(res, int(q.get("resourceVersion", ["0"])[0]))
                    if self._wants_binary():
                        # encode-once list: splice the watch cache's
                        # per-object blobs instead of re-serializing the
                        # full object set per request
                        return self._send_raw(
                            200,
                            server.list_frame(res),
                            wire_codec.CT_BINARY,
                            "binary",
                        )
                    return self._json(200, server.list_payload(res))
                if parts == ["healthz"]:
                    return self._json(200, {"ok": True})
                return self._json(404, {"error": "not found"})

            def _watch(self, res: str, rv: int) -> None:
                self._acct = None  # a stream, not a request latency
                self._end_span()
                cache = server.caches[res]
                # join the watcher registry: fanout lag is the cache head
                # rv minus this stream's delivered rv, scraped on demand
                with cache.cond:
                    cache._watcher_seq += 1
                    wid = cache._watcher_seq
                    cache.watchers[wid] = rv
                try:
                    self._watch_stream(cache, rv, wid, self._wants_binary())
                finally:
                    with cache.cond:
                        cache.watchers.pop(wid, None)

            def _watch_stream(self, cache, rv: int, wid: int, binary: bool) -> None:
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    wire_codec.CT_BINARY if binary else "application/json",
                )
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                codec = "binary" if binary else "json"

                def chunk_raw(data: bytes) -> bool:
                    try:
                        self.wfile.write(hex(len(data))[2:].encode() + b"\r\n")
                        self.wfile.write(data + b"\r\n")
                        self.wfile.flush()
                        server._note_wire(codec, "tx", len(data))
                        return True
                    except (BrokenPipeError, ConnectionError, OSError):
                        return False

                def chunk(payload: dict) -> bool:
                    # control frames (bookmark/410) — built per stream,
                    # they carry stream-local state
                    if binary:
                        return chunk_raw(wire_codec.encode_frame(payload))
                    return chunk_raw((json.dumps(payload) + "\n").encode())

                while True:
                    events = cache.since(rv, timeout=BOOKMARK_INTERVAL_S)
                    if events is None:
                        chunk({"type": "ERROR", "code": 410})
                        break
                    if not events:
                        if not chunk({"type": "BOOKMARK", "rv": rv}):
                            return
                        continue
                    # coalesced emission: ONE chunked write carries every
                    # pending event's pre-serialized bytes — a burst of N
                    # events costs one write+flush instead of N, and the
                    # bytes are the SHARED per-event encoding (binary
                    # frames or memoized JSON lines), never re-serialized
                    # per watcher
                    rv = events[-1].rv
                    cache.watchers[wid] = rv  # plain store — progress report
                    payload = (
                        b"".join(e.frame for e in events)
                        if binary
                        else b"".join(e.json_line for e in events)
                    )
                    if not chunk_raw(payload):
                        return
                try:
                    self.wfile.write(b"0\r\n\r\n")
                except OSError:
                    pass

            def do_POST(self):  # noqa: N802
                self._begin("POST")
                parts = [p for p in urlparse(self.path).path.split("/") if p]
                body = self._read_body()
                if len(parts) == 3 and parts[2] in ("nodes", "pods"):
                    mk = (
                        server._create_node
                        if parts[2] == "nodes"
                        else server._create_pod
                    )
                    if isinstance(body, dict) and "items" in body:
                        # bulk create: per-item results (null = created/
                        # idempotent-ok) so conflicts inside a batch are
                        # never silently reported as created
                        results = []
                        for env in body["items"]:
                            code, payload = mk(decode(env))
                            results.append(None if code < 400 else payload)
                        n_err = sum(1 for r in results if r is not None)
                        return self._json(
                            207 if n_err else 201,
                            {"ok": n_err == 0, "results": results},
                        )
                    code, payload = mk(decode(body))
                    return self._json(code, payload)
                if len(parts) == 3 and parts[2] == "bindings":
                    # BULK binding write: the slice is ONE store
                    # transaction under the server lock (bind_txn) — the
                    # batch-first extension of assignPod (storage.go:169);
                    # per-item statuses come back so the scheduler can
                    # unwind exactly the pods that failed
                    items = [
                        (item.get("uid"), item.get("node"))
                        for item in body.get("items", [])
                    ]
                    ann_lock = annotation("apiserver.lock_wait").begin()
                    with server._mu:
                        ann_lock.end()
                        results = server.bind_txn(items)
                    return self._json(200, {"results": results})
                if len(parts) == 5 and parts[2] == "pods" and parts[4] == "binding":
                    uid = unquote(parts[3])
                    # check-and-bind under the server lock: concurrent
                    # binding POSTs (two active schedulers) must serialize,
                    # and store-level failures translate to API statuses
                    # like assignPod's CAS conflict (storage.go:254)
                    with server._mu:
                        pod = server.api.pods.get(uid)
                        if pod is None:
                            return self._json(
                                404, {"error": f"pod {uid} not found"}
                            )
                        # the store's CAS is the authority (assignPod,
                        # storage.go:254): a conflicting node → 409, a
                        # same-node rebind is idempotent — which makes the
                        # client's transport-level POST retry safe when the
                        # first attempt succeeded but the response was lost
                        try:
                            server.api.bind(pod, body["node"])
                        except RuntimeError as e:
                            # carry the existing binding (see the bulk
                            # route): conflict-on-retry where the node
                            # matches is the client's success signal
                            return self._json(
                                409, {"error": str(e), "node": pod.node_name}
                            )
                        except KeyError as e:
                            return self._json(404, {"error": str(e)})
                    return self._json(201, {"ok": True})
                return self._json(404, {"error": "not found"})

            def do_PUT(self):  # noqa: N802
                self._begin("PUT")
                parts = [p for p in urlparse(self.path).path.split("/") if p]
                body = self._read_body()
                if len(parts) == 4 and parts[2] == "nodes":
                    server.api.update_node(decode(body))
                    return self._json(200, {"ok": True})
                if len(parts) == 4 and parts[2] == "leases":
                    # Lease CAS (resourcelock/leaselock.go over the wire):
                    # stale resourceVersion → 409, the elector backs off
                    from kubernetes_tpu.util.leases import lease_from_wire

                    rec = lease_from_wire(body)
                    if server.api.lease_store.update(unquote(parts[3]), rec):
                        return self._json(
                            200,
                            {"ok": True, "resourceVersion": rec.resource_version + 1},
                        )
                    return self._json(409, {"error": "lease CAS conflict"})
                return self._json(404, {"error": "not found"})

            def do_PATCH(self):  # noqa: N802
                self._begin("PATCH")
                parts = [p for p in urlparse(self.path).path.split("/") if p]
                body = self._read_body()
                if len(parts) == 5 and parts[2] == "pods" and parts[4] == "status":
                    # read-modify-write under the server lock: concurrent
                    # status patches (nomination vs kubelet phase report)
                    # must not resurrect each other's stale fields
                    with server._mu:
                        uid = unquote(parts[3])
                        pod = server.api.pods.get(uid)
                        if pod is None:
                            return self._json(404, {"error": "not found"})
                        if "nominatedNodeName" in body or "phase" in body:
                            # never mutate the store's instance directly —
                            # the store computes its own old/new delta
                            import copy as _copy

                            patched = _copy.copy(pod)
                            if "nominatedNodeName" in body:
                                patched.nominated_node_name = body[
                                    "nominatedNodeName"
                                ]
                            if "phase" in body:
                                patched.phase = body["phase"]
                            server.api.patch_pod_status(patched)
                    return self._json(200, {"ok": True})
                if len(parts) == 5 and parts[2] == "nodes" and parts[4] == "status":
                    # the kubelet heartbeat write (node status subresource):
                    # Ready condition + lastHeartbeatTime — atomic RMW
                    # under the server lock so a concurrent taint PUT is
                    # never erased by a pre-taint copy
                    with server._mu:
                        name = unquote(parts[3])
                        node = server.api.nodes.get(name)
                        if node is None:
                            return self._json(404, {"error": "not found"})
                        import copy as _copy

                        patched = _copy.copy(node)
                        if "ready" in body:
                            patched.ready = bool(body["ready"])
                        if "lastHeartbeat" in body:
                            patched.last_heartbeat = float(body["lastHeartbeat"])
                        server.api.update_node(patched)
                    return self._json(200, {"ok": True})
                if len(parts) == 4 and parts[2] == "nodes":
                    # ATOMIC taint/readiness patch — the node-lifecycle
                    # controller's write shape.  Server-side RMW under the
                    # lock: the controller's view may be stale, but only
                    # the named taints/readiness change; heartbeats written
                    # concurrently are preserved (nodes carry no
                    # resourceVersion, so client-side full-object PUTs
                    # would silently regress them)
                    with server._mu:
                        name = unquote(parts[3])
                        node = server.api.nodes.get(name)
                        if node is None:
                            return self._json(404, {"error": "not found"})
                        import copy as _copy

                        from kubernetes_tpu.api.types import Taint

                        patched = _copy.copy(node)
                        remove = set(body.get("removeTaintKeys", []))
                        taints = tuple(
                            t for t in patched.taints if t.key not in remove
                        )
                        for t in body.get("addTaints", []):
                            if not any(x.key == t["key"] for x in taints):
                                taints = taints + (
                                    Taint(
                                        key=t["key"],
                                        value=t.get("value", ""),
                                        effect=t.get("effect", "NoSchedule"),
                                    ),
                                )
                        patched.taints = taints
                        if "ready" in body:
                            patched.ready = bool(body["ready"])
                        server.api.update_node(patched)
                    return self._json(200, {"ok": True})
                return self._json(404, {"error": "not found"})

            def do_DELETE(self):  # noqa: N802
                self._begin("DELETE")
                parts = [p for p in urlparse(self.path).path.split("/") if p]
                if len(parts) == 4 and parts[2] == "pods":
                    server.api.delete_pod(unquote(parts[3]))
                    return self._json(200, {"ok": True})
                if len(parts) == 4 and parts[2] == "nodes":
                    server.api.delete_node(unquote(parts[3]))
                    return self._json(200, {"ok": True})
                return self._json(404, {"error": "not found"})

        class _Server(ThreadingHTTPServer):
            # registration storms open many sockets faster than accept()
            # drains them while the scheduler compiles — the default
            # backlog of 5 RSTs the overflow
            request_queue_size = 256
            daemon_threads = True

        self.http = _Server((host, port), Handler)
        self.port = self.http.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # ----- store access -----------------------------------------------------

    def _record(self, res: str, etype: str, obj) -> None:
        self._record_many(res, etype, (obj,))

    def _record_many(self, res: str, etype: str, objs) -> None:
        """``objs`` enter the watch cache as one append (consecutive rvs,
        one wake-up).  They may be borrowed from the store: each is
        serialised here and not kept.  A generator, so that an envelope
        is built as the window drops an old one: a slice's worth of new
        containers ahead of the frees would only feed the collector."""
        last = self.caches[res].record_many(
            etype,
            (
                (encode(obj), obj.uid if isinstance(obj, Pod) else obj.name)
                for obj in objs
            ),
        )
        cp = self.cp
        if cp is not None and cp.enabled:
            # the api_write breadcrumbs: each event's rv + its watch-cache
            # entry time — the root of every pod's causal pipeline chain
            cp.note_api_write_many(
                res, zip(range(last - len(objs) + 1, last + 1), objs)
            )

    def bind_txn(self, items) -> list:
        """One bulk binding POST, under ``_mu``: the store applies the
        slice as one transaction (``FakeCluster.bind_many``) and this
        server's batch handler puts its MODIFIED events into the watch
        cache before the store returns — so a bind is acknowledged only
        after the store holds it and its event is replayable."""
        with annotation("apiserver.bind_txn", items=len(items)):
            results = self.api.bind_many(items)
            self.bulk_bind_txns += 1
            self.bulk_bind_items += len(items)
        return results

    @property
    def bulk_bind_fallback_items(self) -> int:
        """Per-item deliveries the store made, in bulk transactions, to
        subscribers that registered no batch handler (0 where this server
        is the store's only subscriber)."""
        return self.api.bind_many_fallback_items

    def _note_wire(self, codec: str, direction: str, n: int) -> None:
        if not n:
            return
        key = (codec, direction)
        with self._wire_mu:
            self.wire_bytes[key] = self.wire_bytes.get(key, 0) + n

    # Creates are IDEMPOTENT for replays of the same SPEC (the client's
    # transport-level POST retry can re-send a create whose response was
    # lost — by then the server may already have written status fields)
    # and 409 AlreadyExists for conflicting specs — no duplicate ADDED
    # event ever reaches the watchers.
    @staticmethod
    def _spec_wire(obj, status_fields):
        d = dict(encode(obj))
        body = d.get("object", d)
        for f in status_fields:
            body.pop(f, None)
        return d

    def _create_node(self, node):
        status = ("ready", "lastHeartbeat", "last_heartbeat")
        with self._mu:
            cur = self.api.nodes.get(node.name)
            if cur is not None:
                if self._spec_wire(cur, status) == self._spec_wire(node, status):
                    return 200, {"ok": True, "idempotent": True}
                return 409, {"error": f"node {node.name} already exists"}
            self.api.create_node(node)
        return 201, {"ok": True}

    def _create_pod(self, pod):
        status = (
            "nodeName",
            "node_name",
            "phase",
            "nominatedNodeName",
            "nominated_node_name",
            "startTime",
            "start_time",
        )
        with self._mu:
            cur = self.api.pods.get(pod.uid)
            if cur is not None:
                if self._spec_wire(cur, status) == self._spec_wire(pod, status):
                    return 200, {"ok": True, "idempotent": True}
                return 409, {"error": f"pod {pod.uid} already exists"}
            self.api.create_pod(pod)
        return 201, {"ok": True}

    def list_payload(self, res: str) -> dict:
        """Consistent list: snapshot + the rv of the last event applied
        (reflector lists at this rv, then watches from it).  Only the
        snapshot + rv capture happens under the watch-cache lock; encoding
        10k objects there would stall every writer and watch fan-out for
        the duration (replayed events are idempotent on the client, so an
        event racing the encode is harmless)."""
        cache = self.caches[res]
        with cache.cond:
            # dict.copy() is atomic under the GIL — handler threads mutate
            # the store concurrently and bare .values() iteration would
            # raise "dictionary changed size during iteration"
            store = self.api.nodes if res == "nodes" else self.api.pods
            snapshot = store.copy()
            rv = cache.rv
        return {
            "resourceVersion": rv,
            "items": [encode(obj) for obj in snapshot.values()],
        }

    def list_frame(self, res: str) -> bytes:
        """The binary list response: same snapshot+rv discipline as
        ``list_payload``, but items are the watch cache's per-object
        nested blobs SPLICED into one frame — encode cost per request is
        O(items) concatenation, not O(items) serialization.  An object
        created before this server attached (no recorded event yet) falls
        back to a one-off encode; an object whose latest MODIFIED hasn't
        fanned out yet serves its previous blob, which the reflector's
        idempotent event replay corrects — the same race the JSON path
        tolerates in the other direction."""
        cache = self.caches[res]
        with cache.cond:
            store = self.api.nodes if res == "nodes" else self.api.pods
            snapshot = store.copy()
            frames = dict(cache.obj_frames)
            rv = cache.rv
        blobs = []
        for key, obj in snapshot.items():
            blob = frames.get(key)
            if blob is None:
                blob = wire_codec.encode_nested(encode(obj))
            blobs.append(blob)
        return wire_codec.encode_list_frame(rv, blobs)

    # ----- lifecycle --------------------------------------------------------

    def start(self) -> "ApiServer":
        self._thread = threading.Thread(
            target=self.http.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self.http.shutdown()
        if self._thread is not None:
            self._thread.join(timeout=5)
