"""TcpShuffleTransport: the cross-process / cross-host shuffle plane.

Reference mapping (SURVEY §2.6, §5.8): the UCX transport module — a TCP
management/metadata plane plus a tagged data plane moving partition
buffers peer-to-peer, with an inflight-bytes throttle
(UCX.scala:192-328, UCXShuffleTransport.scala:365-391) — behind the
`RapidsShuffleTransport` SPI.  The TPU engine's cross-slice analog is a
host TCP plane (DCN-style): map output stays spillable in the local
store (the `LocalShuffleTransport` it wraps), a server thread serves
partition ranges on demand, and peers fetch with a length-prefixed,
type-tagged frame protocol:

    request  (JSON frame): {"op": "fetch", "shuffle_id": .., "part_id":
              .., "lo": .., "hi": .., "window": <client ack window>,
              "crc": [<checksum algos the client can verify>]}
              | {"op": "meta", "shuffle_id": ..}
    response: [8-byte big-endian length][1-byte tag][payload] frames:
              tag 0x03 = JSON header/metadata (fetch headers carry the
              server's codec and its checksum pick, so compression AND
              integrity are negotiated, not assumed), 0x00 = batch data
              (Arrow IPC bytes, codec-compressed with a 4-byte raw-size
              prefix when the header says so, prefixed with a 4-byte
              CRC32C/CRC32 when a checksum was negotiated), 0x01 = end
              of stream, 0x02 = server-side error (payload is the
              message — a store failure reaches the client as a
              diagnosable ShuffleFetchError, not a connection reset).

The server throttles at the CLIENT-declared ``window`` (carried in the
request), so both endpoints count the same bytes and a conf mismatch
cannot deadlock the ack exchange.  Request/ack frames are capped at 64
KiB (``_MAX_CTRL_FRAME``): a desynced peer lying in a control frame's
length prefix cannot make the server attempt a multi-GiB allocation.
Transport failures (reset, stall past the deadline, checksum mismatch)
raise the retryable ``ShuffleTransportError``; shuffle/retry.py wraps
the client in a resumable backoff ladder with a per-peer circuit
breaker, and spark_rapids_tpu/faults.py can inject failures
deterministically at every seam.

Within a slice the mesh collective path (parallel/mesh_shuffle.py) is
the ICI plane; this module is the inter-process/DCN plane.  The
listener binds ``spark.rapids.shuffle.tcp.bindAddress`` (loopback by
default; set 0.0.0.0 — plus advertiseAddress — for real multi-host).
"""
from __future__ import annotations

import json
import socket
import struct
import threading
import time
import zlib
from collections import deque
from typing import Iterable

from spark_rapids_tpu.conf import ConfEntry, register, parse_bytes, _bool
# obs.registry is dependency-free (stdlib only) — safe at module level
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.shuffle.compression import get_codec
# re-exported for backward compatibility: these historically lived here
from spark_rapids_tpu.shuffle.errors import (MapOutputLostError,
                                             ShuffleFetchError,
                                             ShuffleTransportError)
from spark_rapids_tpu.shuffle.local import LocalShuffleTransport
from spark_rapids_tpu.shuffle.serializer import deserialize_batch

__all__ = ["TcpShuffleTransport", "TcpShuffleServer", "ShuffleFetchError",
           "ShuffleTransportError", "MapOutputLostError", "fetch_remote",
           "remote_partition_sizes"]

TCP_PORT = register(ConfEntry(
    "spark.rapids.shuffle.tcp.port", 0,
    "Listen port for the TCP shuffle server (0 = ephemeral). The bound "
    "address is exposed as transport.address, the analog of the UCX "
    "management port carried in MapStatus "
    "(RapidsShuffleInternalManager.scala:173-186).", conv=int))
TCP_BIND_ADDRESS = register(ConfEntry(
    "spark.rapids.shuffle.tcp.bindAddress", "127.0.0.1",
    "Interface the TCP shuffle server binds. Loopback by default "
    "(single-host); set 0.0.0.0 (with advertiseAddress) so peers on "
    "other hosts can fetch over DCN."))
TCP_ADVERTISE_ADDRESS = register(ConfEntry(
    "spark.rapids.shuffle.tcp.advertiseAddress", "",
    "Host peers should dial (when binding 0.0.0.0 the bound address is "
    "not routable). Empty = the bind address."))
TCP_INFLIGHT_LIMIT = register(ConfEntry(
    "spark.rapids.shuffle.tcp.maxBytesInFlight", 64 << 20,
    "Client fetch window: the server sends at most this many payload "
    "bytes ahead of the client's acks. Carried in each fetch request, "
    "so both endpoints always use the same window (reference "
    "inflight-bytes throttle, UCXShuffleTransport.scala:365-391).",
    conv=parse_bytes))
TCP_TIMEOUT = register(ConfEntry(
    "spark.rapids.shuffle.tcp.timeoutSeconds", 120,
    "Socket timeout for shuffle fetches: a wedged peer raises "
    "ShuffleFetchError instead of hanging the reduce task forever "
    "(reference: fetch timeout via spark.network.timeout, "
    "GpuShuffleEnv.scala:60-62, propagated through "
    "RapidsShuffleIterator).", conv=float))
SOCKET_TIMEOUT = register(ConfEntry(
    "spark.rapids.shuffle.socketTimeout", 0.0,
    "Per-read/write timeout in seconds on established shuffle data "
    "connections, applied on BOTH ends: the client's fetch socket and "
    "the server's accepted connections. A peer that accepts and then "
    "stalls mid-stream surfaces as a retryable ShuffleFetchError after "
    "this long instead of holding the connection (and a serve thread) "
    "until tcp.timeoutSeconds. 0 inherits tcp.timeoutSeconds. Set it "
    "well below the backoff ladder's total budget so a hung peer "
    "converts into retries the circuit breaker can count.", conv=float))
TCP_CHECKSUM = register(ConfEntry(
    "spark.rapids.shuffle.tcp.checksumEnabled", True,
    "Per-data-frame integrity checksum (CRC32C when the C binding is "
    "available, CRC32 otherwise), negotiated through the fetch header "
    "so old/new peers interoperate: the client advertises the "
    "algorithms it knows, the server echoes its pick and prefixes each "
    "frame with the 4-byte checksum. Corruption surfaces as a "
    "retryable ShuffleFetchError at the frame boundary instead of a "
    "poisoned Arrow deserialize. (reference: UCX delegates integrity "
    "to the fabric; a DCN-style TCP plane must carry its own)",
    conv=_bool))

_LEN = struct.Struct(">Q")
_TAG_DATA, _TAG_END, _TAG_ERROR, _TAG_JSON = b"\x00", b"\x01", b"\x02", b"\x03"
#: frame sanity floor: a frame is one batch's bytes; the effective cap
#: is max(this, 2x spark.rapids.sql.batchSizeBytes) so oversized-batch
#: configs stay fetchable while a desynced/non-protocol peer still gets
#: a clean error instead of a garbage-length allocation
_MAX_FRAME_MIN = 2 << 30
#: request/ack frames are small JSON — a desynced or malicious peer
#: must not be able to make the server attempt a multi-GiB allocation
#: by lying in a control frame's length prefix
_MAX_CTRL_FRAME = 64 << 10

#: frame checksum algorithms this endpoint can verify, in preference
#: order; negotiation picks the first name both peers know, so a build
#: without the C crc32c binding still interoperates via zlib's crc32
_CRC_ALGOS: dict = {}
try:
    import google_crc32c as _gcrc32c

    _CRC_ALGOS["crc32c"] = _gcrc32c.value
except ImportError:  # pragma: no cover - env without the binding
    pass
_CRC_ALGOS["crc32"] = zlib.crc32
_CRC = struct.Struct(">I")

#: codec names this endpoint can DECODE, advertised in fetch requests
#: (resolved once; zstd only when its binding imports)
_CLIENT_CODECS: "list[str] | None" = None


def _client_codecs() -> "list[str]":
    """Codecs the client side can inflate, carried in the fetch request
    as ``codecs`` so a server whose store compresses with something the
    client lacks refuses the stream with a diagnosable error frame
    instead of letting the client die inside get_codec/decompress.
    Old peers send/understand no ``codecs`` key — same interop pattern
    as the ``crc`` negotiation."""
    global _CLIENT_CODECS
    if _CLIENT_CODECS is None:
        names = ["none", "lz4"]
        try:
            import zstandard  # noqa: F401

            names.append("zstd")
        except ImportError:  # pragma: no cover - env without zstd
            pass
        _CLIENT_CODECS = names
    return _CLIENT_CODECS


def _max_frame(conf=None) -> int:
    if conf is None:
        return _MAX_FRAME_MIN
    return max(_MAX_FRAME_MIN, 2 * conf.batch_size_bytes)


#: error-frame prefix carrying a structured terminal-loss payload: the
#: server's store lost map outputs, and the client must surface WHICH
#: ones so stage recovery can recompute exactly those (not retry)
_LOST_MARKER = "MAP_OUTPUT_LOST "


def _raise_error_frame(body: bytes, shuffle_id, part_id: int) -> None:
    """Decode a _TAG_ERROR payload into the right exception class: a
    MAP_OUTPUT_LOST marker means terminal data loss at the peer (raise
    MapOutputLostError with the lost map ids), anything else is a plain
    server-side ShuffleFetchError."""
    text = body.decode()
    if text.startswith(_LOST_MARKER):
        try:
            payload = json.loads(text[len(_LOST_MARKER):])
        except ValueError:
            raise ShuffleFetchError(text) from None
        raise MapOutputLostError.parse(shuffle_id, part_id, payload)
    raise ShuffleFetchError(text)


def _send_frame(sock: socket.socket, tag: bytes, payload: bytes = b"") -> None:
    sock.sendall(_LEN.pack(len(payload) + 1) + tag + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket,
                max_frame: int = _MAX_FRAME_MIN) -> tuple[bytes, bytes]:
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if n < 1 or n > max_frame:
        raise ConnectionError(f"bad frame length {n} (desynced or "
                              "non-protocol peer)")
    body = _recv_exact(sock, n)
    return body[:1], body[1:]


class TcpShuffleServer:
    """Serves a LocalShuffleTransport's map output over TCP (reference
    RapidsShuffleServer.scala:67: serve metadata + buffer-send requests
    from the catalog-backed store)."""

    def __init__(self, store: LocalShuffleTransport, bind: str = "127.0.0.1",
                 port: int = 0, advertise: str = ""):
        self._store = store
        # deterministic fault plan (spark.rapids.test.faults), owned by
        # the store so counters span this server's whole lifetime
        self._faults = getattr(store, "faults", None)
        self.metrics = {"meta_requests": 0, "fetch_requests": 0,
                        "data_frames_sent": 0, "bytes_sent": 0,
                        "faults_injected": 0, "traced_fetches": 0}
        # propagated trace headers from peers' fetch requests (bounded):
        # the serving side's record that remote work belonged to a given
        # originating query_id/trace_id
        self.trace_log: deque = deque(maxlen=256)
        self._reg_source = get_registry().register_object_source(
            f"shuffle.server.{id(self):x}", self)
        # read/write timeout for accepted connections: a client that
        # connects and then wedges must not pin a serve thread forever
        settings = getattr(getattr(store, "conf", None), "settings", {})
        st = SOCKET_TIMEOUT.get(settings)
        if not st or st <= 0:
            st = TCP_TIMEOUT.get(settings)
        self._sock_timeout = st if st and st > 0 else None
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((bind, port))
        self._sock.listen(16)
        host, bound_port = self._sock.getsockname()
        self.address = (advertise or host, bound_port)
        self._closed = threading.Event()
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True, name="tpu-shuffle-srv")
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            # a timed-out read raises TimeoutError (an OSError), which
            # the _serve handlers already treat as "drop the connection"
            conn.settimeout(self._sock_timeout)
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn: socket.socket) -> None:
        try:
            with conn:
                # enginelint: disable=RL004 (per-connection serve loop; peer close raises ConnectionError and server shutdown closes the socket)
                while True:
                    try:
                        _, body = _recv_frame(conn, _MAX_CTRL_FRAME)
                        req = json.loads(body.decode())
                    except (ConnectionError, ValueError):
                        return
                    try:
                        self._serve_one(conn, req)
                    except (ConnectionError, OSError):
                        return
                    except MapOutputLostError as e:
                        # terminal loss: ship the structured payload so
                        # the reader's stage-recovery layer learns WHICH
                        # map outputs died, not just that the fetch failed
                        _send_frame(conn, _TAG_ERROR, (
                            _LOST_MARKER + json.dumps(
                                {"shuffle_id": e.shuffle_id,
                                 "part_id": e.part_id,
                                 "lost": {str(k): v
                                          for k, v in e.lost.items()},
                                 "detail": "reported by peer",
                                 "observed_empty":
                                     e.observed_empty})).encode())
                    # enginelint: disable=RL001 (failure is surfaced to the peer as an error frame, not swallowed)
                    except Exception as e:  # noqa: BLE001 - sent to peer
                        # store/codec failures must reach the client as a
                        # diagnosable error frame, not a connection reset
                        _send_frame(conn, _TAG_ERROR,
                                    f"{type(e).__name__}: {e}".encode())
        except (ConnectionError, OSError):
            pass

    def _serve_one(self, conn: socket.socket, req: dict) -> None:
        if req.get("op") == "meta":
            self.metrics["meta_requests"] += 1
            sizes = self._store.partition_sizes(req["shuffle_id"])
            batches = {str(p): self._store.batch_sizes(req["shuffle_id"], p)
                       for p in sizes}
            _send_frame(conn, _TAG_JSON, json.dumps(
                {"sizes": {str(k): v for k, v in sizes.items()},
                 "batch_sizes": batches,
                 "codec": self._store.codec_name}).encode())
            return
        if req.get("op") != "fetch":
            _send_frame(conn, _TAG_ERROR,
                        f"unknown op {req.get('op')!r}".encode())
            return
        self.metrics["fetch_requests"] += 1
        if self._faults is not None:
            act = self._faults.check("shuffle.peer.hang",
                                     shuffle=req["shuffle_id"],
                                     part=req["part_id"])
            if act is not None:
                # accepted-then-stalled peer: hold the connection open
                # sending NOTHING (no header, no error frame) until the
                # client's socketTimeout trips or this server closes —
                # the exact wedge satellite 1's data-socket timeout
                # exists to convert into a retryable ShuffleFetchError
                self.metrics["faults_injected"] += 1
                self._closed.wait(act.param("seconds", 3600.0))
                return
        # trace propagation: a new peer carries its query's ids in the
        # request; record them, emit a serve event re-parented onto the
        # propagated span when this process has a live tracer, and echo
        # the header back.  An old peer sends no "trace" key and is
        # served exactly as before.
        tr = req.get("trace") or None
        if isinstance(tr, dict):
            self.metrics["traced_fetches"] += 1
            self.trace_log.append({
                "query_id": tr.get("query_id"),
                "trace_id": tr.get("trace_id"),
                "span_id": tr.get("span_id"),
                "shuffle_id": req["shuffle_id"], "part_id": req["part_id"],
                "lo": req.get("lo", 0), "hi": req.get("hi")})
            try:
                ctx = getattr(self._store, "ctx", None)
                tracer = ctx.tracer if ctx is not None else None
            # enginelint: disable=RL001 (tracing is best-effort; serving proceeds without a span)
            except Exception:
                tracer = None
            if tracer is not None:
                tracer.event("shuffle.serve", "shuffle",
                             parent_id=tr.get("span_id"),
                             origin_query_id=tr.get("query_id"),
                             origin_trace_id=tr.get("trace_id"),
                             shuffle=str(req["shuffle_id"]),
                             part=req["part_id"],
                             lo=req.get("lo", 0), hi=req.get("hi"))
        window = int(req.get("window") or TCP_INFLIGHT_LIMIT.default)
        # codec negotiation: a new client lists the codecs it can
        # decode; when this store's codec is not among them the stream
        # is refused with a diagnosable error frame — the client would
        # otherwise die inside decompress on the first data frame.  An
        # old peer sends no "codecs" key and is served as before.
        accepts = req.get("codecs")
        if accepts is not None and self._store.codec_name not in accepts:
            self.metrics["codec_rejects"] = \
                self.metrics.get("codec_rejects", 0) + 1
            _send_frame(conn, _TAG_ERROR, (
                f"shuffle codec {self._store.codec_name!r} not accepted "
                f"by client (client accepts {list(accepts)}); align "
                "spark.rapids.shuffle.compression.codec across peers"
            ).encode())
            return
        # checksum negotiation: the client advertises the algorithms it
        # can verify; pick the first this server also knows and echo it
        # in the header.  An old peer sends/understands no "crc" key and
        # gets the unprefixed frames it expects.
        offered = req.get("crc") or []
        if isinstance(offered, str):
            offered = [offered]
        crc_name = next((n for n in offered if n in _CRC_ALGOS), None)
        header = {"codec": self._store.codec_name}
        if crc_name is not None:
            header["crc"] = crc_name
        if isinstance(tr, dict):
            header["trace"] = tr
        crc_fn = _CRC_ALGOS.get(crc_name)
        _send_frame(conn, _TAG_JSON, json.dumps(header).encode())
        sent_window = 0
        for i, raw in enumerate(self._store.fetch_partition_serialized(
                req["shuffle_id"], req["part_id"],
                req.get("lo", 0), req.get("hi"))):
            payload = raw if crc_fn is None else \
                _CRC.pack(crc_fn(raw) & 0xFFFFFFFF) + raw
            if self._faults is not None:
                act = self._faults.check(
                    "tcp.server.frame", shuffle=req["shuffle_id"],
                    part=req["part_id"], frame=i)
                if act is not None:
                    self.metrics["faults_injected"] += 1
                    if act.action == "reset":
                        # abrupt mid-stream close: the client sees a
                        # peer reset, never an END or error frame
                        raise ConnectionError("injected fault: reset")
                    if act.action == "error":
                        _send_frame(conn, _TAG_ERROR,
                                    b"injected fault: server error frame")
                        return
                    if act.action == "stall":
                        time.sleep(act.param("seconds", 5.0))
                    elif act.action == "corrupt":
                        # flip one seeded byte AFTER the checksum was
                        # computed: in-transit corruption as the client
                        # verifier sees it
                        flipped = bytearray(payload)
                        flipped[act.rng.randrange(len(flipped))] ^= 0xFF
                        payload = bytes(flipped)
            _send_frame(conn, _TAG_DATA, payload)
            self.metrics["data_frames_sent"] += 1
            self.metrics["bytes_sent"] += len(payload)
            sent_window += len(payload)
            if sent_window >= window:
                # wait for the client before sending further frames
                # (inflight throttle at the client-declared window)
                tag, _ = _recv_frame(conn, _MAX_CTRL_FRAME)
                if tag != _TAG_JSON:
                    return
                sent_window = 0
        _send_frame(conn, _TAG_END)

    def close(self) -> None:
        self._closed.set()
        get_registry().unregister_source(self._reg_source)
        try:
            # close() alone leaves the accept loop blocked in accept()
            # on the dead fd for good; shutdown wakes it
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass


class TcpShuffleTransport(LocalShuffleTransport):
    """SPI transport = local spillable store + TCP server for peers.

    In-process consumers read straight from the store (the reference's
    local-block path, RapidsCachingReader.scala:49); remote consumers
    connect to ``transport.address`` and stream frames (`fetch_remote`).
    """

    def __init__(self, conf, ctx=None):
        super().__init__(conf, ctx)
        self._server = TcpShuffleServer(
            self, bind=conf.get(TCP_BIND_ADDRESS),
            port=conf.get(TCP_PORT),
            advertise=conf.get(TCP_ADVERTISE_ADDRESS))
        self.address = self._server.address

    @property
    def server_metrics(self) -> dict:
        return self._server.metrics

    def fetch_from(self, address, shuffle_id: "int | str", part_id: int,
                   lo: int = 0, hi: int | None = None,
                   device: bool = True) -> Iterable:
        """Client entry honoring this transport's conf: window, timeout,
        checksum, and the retry/backoff/circuit-breaker ladder all come
        from the conf (reference: the transport owns its inflight
        throttle and its failure policy, not the call site)."""
        from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
        ctx = getattr(self, "ctx", None)
        tracer = ctx.tracer if ctx is not None else None
        trace = tracer.trace_header() if tracer is not None else None
        lifecycle = ctx.lifecycle if ctx is not None else None
        return fetch_remote_with_retry(address, shuffle_id, part_id,
                                       lo=lo, hi=hi, device=device,
                                       conf=self.conf, faults=self.faults,
                                       tracer=tracer, trace=trace,
                                       lifecycle=lifecycle)

    def close(self) -> None:
        self._server.close()
        super().close()


def _resolve_timeout(timeout: float | None) -> float | None:
    """None -> conf default; 0 -> no timeout (blocking), the usual
    convention for disabling it."""
    t = TCP_TIMEOUT.default if timeout is None else float(timeout)
    return t if t > 0 else None


def _check_connect_fault(faults, address) -> None:
    if faults is not None:
        act = faults.check("tcp.client.connect", host=address[0],
                           port=address[1])
        if act is not None:
            raise ConnectionError("injected fault: connect reset")


def remote_partition_sizes(address, shuffle_id: "int | str",
                           timeout: float | None = None,
                           sock_timeout: float | None = None,
                           faults=None) -> tuple[dict, dict]:
    """Metadata plane: (partition_sizes, batch_sizes) from a peer
    (reference MetadataRequest/Response flatbuffer RPC).  A wedged peer
    raises ShuffleFetchError after ``timeout`` seconds (``sock_timeout``
    tightens the per-read deadline once connected — the socketTimeout
    conf); a reset or mid-frame close is wrapped with the same context
    instead of leaking a raw ConnectionError to the reduce task."""
    tmo = _resolve_timeout(timeout)
    try:
        _check_connect_fault(faults, tuple(address))
        with socket.create_connection(tuple(address), timeout=tmo) as sock:
            if sock_timeout is not None and sock_timeout > 0:
                sock.settimeout(sock_timeout)
            _send_frame(sock, _TAG_JSON, json.dumps(
                {"op": "meta", "shuffle_id": shuffle_id}).encode())
            tag, body = _recv_frame(sock)
    except TimeoutError as e:
        raise ShuffleTransportError(
            f"metadata fetch of shuffle {shuffle_id} from {address} "
            f"stalled past its read deadline") from e
    except (ConnectionError, OSError) as e:
        raise ShuffleTransportError(
            f"metadata fetch of shuffle {shuffle_id} from {address} "
            f"failed: {type(e).__name__}: {e}") from e
    if tag == _TAG_ERROR:
        raise ShuffleFetchError(body.decode())
    meta = json.loads(body.decode())
    return ({int(k): v for k, v in meta["sizes"].items()},
            {int(k): v for k, v in meta["batch_sizes"].items()})


def fetch_remote(address, shuffle_id: "int | str", part_id: int, lo: int = 0,
                 hi: int | None = None, device: bool = True,
                 inflight_limit: int | None = None,
                 max_frame: int = _MAX_FRAME_MIN,
                 timeout: float | None = None,
                 sock_timeout: float | None = None,
                 checksum: bool = True, faults=None,
                 trace: dict | None = None, raw: bool = False) -> Iterable:
    """Data plane: stream one reduce partition's batches from a peer
    (reference RapidsShuffleClient.scala: TransferRequest -> bounce
    buffers -> reassembled device buffers).  The wire codec and frame
    checksum come from the server's response header — never assumed by
    the client.  Every transport failure — a stall past ``timeout``
    (connect, send, or receive; 0 disables the deadline), a reset or
    mid-frame close, a frame failing its negotiated checksum — raises
    ShuffleTransportError (retryable; see shuffle/retry.py) instead of
    wedging or poisoning the reduce task.

    ``raw=True`` yields the decompressed Arrow IPC bytes of each slot
    instead of deserialized batches — the graceful-drain migration path
    relays a retiring worker's slots into a survivor's store without a
    decode/re-encode round trip (cluster/worker.py)."""
    window = int(inflight_limit or TCP_INFLIGHT_LIMIT.default)
    tmo = _resolve_timeout(timeout)
    peer_label = ":".join(str(x) for x in tuple(address))
    bytes_fetched = 0
    try:
        _check_connect_fault(faults, tuple(address))
        with socket.create_connection(tuple(address), timeout=tmo) as sock:
            if sock_timeout is not None and sock_timeout > 0:
                # tighter per-read deadline on the established data
                # connection (spark.rapids.shuffle.socketTimeout): an
                # accepted-then-stalled peer fails fast and retryably
                sock.settimeout(sock_timeout)
            req = {"op": "fetch", "shuffle_id": shuffle_id,
                   "part_id": part_id, "lo": lo, "hi": hi,
                   "window": window, "codecs": _client_codecs()}
            if checksum:
                req["crc"] = list(_CRC_ALGOS)
            if trace:
                # propagation header: the serving side attributes this
                # stream to the originating query_id/trace_id (absent
                # for old callers — same interop pattern as "crc")
                req["trace"] = trace
            _send_frame(sock, _TAG_JSON, json.dumps(req).encode())
            tag, body = _recv_frame(sock)
            if tag == _TAG_ERROR:
                _raise_error_frame(body, shuffle_id, part_id)
            if tag != _TAG_JSON:
                raise ShuffleTransportError(f"bad fetch header tag {tag!r}")
            header = json.loads(body.decode())
            codec_name = header.get("codec", "none")
            try:
                codec = get_codec(codec_name)
            except (ValueError, RuntimeError) as e:
                # negotiation should have caught this server-side; a
                # header naming a codec this build cannot construct is
                # a config/version mismatch, not a transient — surface
                # it terminally with the fix in the message
                err = ShuffleFetchError(
                    f"peer {address} serves shuffle {shuffle_id} with "
                    f"codec {codec_name!r} this client cannot decode "
                    f"(supports {_client_codecs()}): {e}")
                err.terminal = True
                raise err from e
            # handshake record: which codec each fetch stream actually
            # negotiated (tests + diag bundles read this)
            get_registry().inc(f"shuffle.fetch.codec.{codec_name}")
            crc_name = header.get("crc")
            crc_fn = _CRC_ALGOS.get(crc_name)
            if crc_name is not None and crc_fn is None:
                raise ShuffleFetchError(
                    f"peer {address} negotiated unknown frame checksum "
                    f"{crc_name!r} (offered {list(_CRC_ALGOS)})")
            recv_window = 0
            index = lo
            # enginelint: disable=RL004 (frame pump bounded by the socket timeout; END/ERROR frames or ConnectionError exit)
            while True:
                tag, frame = _recv_frame(sock, max_frame)
                if tag == _TAG_END:
                    return
                if tag == _TAG_ERROR:
                    _raise_error_frame(frame, shuffle_id, part_id)
                bytes_fetched += len(frame)
                recv_window += len(frame)
                if recv_window >= window:
                    _send_frame(sock, _TAG_JSON, b"{}")
                    recv_window = 0
                if crc_fn is not None:
                    if len(frame) <= _CRC.size:
                        raise ShuffleTransportError(
                            f"malformed frame: {len(frame)} bytes with a "
                            f"{crc_name} prefix negotiated")
                    (want,) = _CRC.unpack(frame[:_CRC.size])
                    frame = frame[_CRC.size:]
                    got = crc_fn(frame) & 0xFFFFFFFF
                    if got != want:
                        get_registry().inc("shuffle.fetch.checksum_failures")
                        raise ShuffleTransportError(
                            f"frame {index} of shuffle {shuffle_id} part "
                            f"{part_id} from {address} failed its "
                            f"{crc_name} check (sent {want:#010x}, "
                            f"computed {got:#010x}): corrupted in transit")
                if codec is not None:
                    if len(frame) < 4:
                        raise ShuffleFetchError(
                            f"malformed compressed frame: {len(frame)} "
                            "bytes, need >= 4 for the raw-size prefix")
                    (raw_size,) = struct.unpack(">I", frame[:4])
                    if raw_size > max_frame:
                        raise ShuffleFetchError(
                            f"compressed frame claims raw size {raw_size} "
                            f"> max frame {max_frame}")
                    frame = codec.decompress(frame[4:], raw_size)
                yield frame if raw else deserialize_batch(frame,
                                                          device=device)
                index += 1
    except TimeoutError as e:
        raise ShuffleTransportError(
            f"fetch of shuffle {shuffle_id} part {part_id} from "
            f"{address} stalled past its read deadline") from e
    except (ConnectionError, OSError) as e:
        raise ShuffleTransportError(
            f"fetch of shuffle {shuffle_id} part {part_id} from "
            f"{address} failed: {type(e).__name__}: {e}") from e
    finally:
        # flushed once per stream (attempt), whatever way it ends, so
        # per-peer byte movement is visible even for failed attempts
        if bytes_fetched:
            get_registry().inc(f"shuffle.peer.{peer_label}.bytes_fetched",
                               bytes_fetched)
            get_registry().inc("shuffle.fetch.bytes", bytes_fetched)
