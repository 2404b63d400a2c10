"""TCP cross-process shuffle transport.

The reference's accelerated shuffle runs over UCX — endpoint bootstrap on
a TCP management port, tag-addressed transfers, a single progress thread
per endpoint (shuffle-plugin/.../ucx/UCX.scala:70-266,
UCXShuffleTransport.scala:47-105). TPU pods get the same-slice bulk path
"for free" as in-program ICI collectives (parallel/shuffle.py), so the
socket transport's job here is the reference's OTHER path: cross-host /
DCN block service with Spark-compatible failure semantics.

This module is a real-socket implementation of the transport-agnostic
protocol in shuffle/transport.py — the SAME ``ShuffleServer`` handlers
and the SAME ``ShuffleClient`` windowed-chunk/inflight-throttle logic run
over it, so everything the mocked-transport tests established about the
protocol holds across processes:

- framing: 4-byte big-endian length + JSON control message; chunk
  responses carry raw payload bytes after the JSON header,
- server: accept thread + per-connection reader threads that submit into
  ONE progress-queue endpoint (the UCX single-progress-thread model,
  UCX.scala:80-97) — handlers never run concurrently,
- client: one socket per connection object, request/response serialized
  under a lock; socket errors and timeouts surface as TransportError so
  the task iterator converts them to fetch-failures → stage retry
  (RapidsShuffleIterator.scala:242-300).
"""
from __future__ import annotations

import json
import random
import socket
import struct
import threading
from spark_rapids_tpu.utils import lockorder
import time
from typing import Dict, List, Optional

from spark_rapids_tpu.shuffle.meta import BlockId, ShuffleTableMeta
from spark_rapids_tpu.shuffle.transport import (Connection, ShuffleServer,
                                                TransportError, _Endpoint)

_LEN = struct.Struct(">I")
_MAX_FRAME = 256 << 20

# Process-wide transport retry policy (rapids.tpu.shuffle.retry.*):
# connections are created per-peer deep inside the transport registry,
# so the session pushes the knobs here once (configure_retry_from_conf,
# called from runtime.initialize alongside the fault injector) instead
# of threading a conf through every connect().
_retry_policy = {"max_reconnects": 3, "jitter_ms": 10}


def configure_retry(max_reconnects: Optional[int] = None,
                    jitter_ms: Optional[int] = None) -> None:
    """Set the process-wide transport retry policy; None leaves a field
    unchanged. Existing connections keep the policy they were built
    with (one socket, in-flight requests)."""
    if max_reconnects is not None:
        _retry_policy["max_reconnects"] = max(int(max_reconnects), 0)
    if jitter_ms is not None:
        _retry_policy["jitter_ms"] = max(int(jitter_ms), 0)


def configure_retry_from_conf(conf) -> None:
    """Push ``rapids.tpu.shuffle.retry.{maxReconnects,jitterMs}`` into
    the process-wide policy."""
    from spark_rapids_tpu import config as cfg

    configure_retry(
        max_reconnects=conf.get(cfg.SHUFFLE_RETRY_MAX_RECONNECTS),
        jitter_ms=conf.get(cfg.SHUFFLE_RETRY_JITTER_MS))


class Hangup(Exception):
    """Raised from a fault hook to kill the connection without replying —
    the injected-connection-drop primitive for failure tests."""


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        part = sock.recv(n - len(buf))
        if not part:
            raise ConnectionError("peer closed")
        buf.extend(part)
    return bytes(buf)


def _send_frame(sock: socket.socket, header: dict,
                payload: bytes = b"") -> None:
    body = json.dumps(header).encode()
    sock.sendall(_LEN.pack(len(body)) + body +
                 _LEN.pack(len(payload)) + payload)


def _recv_frame(sock: socket.socket):
    (hlen,) = _LEN.unpack(_recv_exact(sock, 4))
    if hlen > _MAX_FRAME:
        raise ConnectionError(f"oversized header {hlen}")
    header = json.loads(_recv_exact(sock, hlen))
    (plen,) = _LEN.unpack(_recv_exact(sock, 4))
    if plen > _MAX_FRAME:
        raise ConnectionError(f"oversized payload {plen}")
    payload = _recv_exact(sock, plen) if plen else b""
    return header, payload


def _block_to_wire(b: BlockId) -> list:
    return [b.shuffle_id, b.map_id, b.partition]


def _block_from_wire(w) -> BlockId:
    return BlockId(int(w[0]), int(w[1]), int(w[2]))


class TcpShuffleServer:
    """Serves one executor's catalog over a listening socket.

    The bootstrap role of the reference's TCP management port: peers
    connect to ``(host, port)`` learned from the map-status topology
    string (RapidsShuffleInternalManager.scala:171-183)."""

    def __init__(self, server: ShuffleServer, host: str = "127.0.0.1",
                 port: int = 0):
        self.server = server
        self._ep = _Endpoint(server)
        self._listener = socket.create_server((host, port))
        self.host, self.port = self._listener.getsockname()[:2]
        self._conns: List[socket.socket] = []
        self._lock = lockorder.make_lock("shuffle.tcp.server")
        self._closed = False
        self._accept_thread = threading.Thread(
            target=self._accept_loop,
            name=f"tcp-shuffle-{server.executor_id}", daemon=True)
        self._accept_thread.start()

    @property
    def address(self):
        return (self.host, self.port)

    def _accept_loop(self):
        while not self._closed:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            with self._lock:
                self._conns.append(conn)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket):
        try:
            while True:
                header, _ = _recv_frame(conn)
                op = header["op"]
                try:
                    if op == "metadata":
                        blocks = [_block_from_wire(w)
                                  for w in header["blocks"]]
                        metas = self._ep.submit("metadata",
                                                blocks).result()
                        _send_frame(conn, {
                            "ok": True,
                            "metas": [m.to_json() for m in metas]})
                    elif op == "chunk":
                        data = self._ep.submit(
                            "chunk", _block_from_wire(header["block"]),
                            int(header["offset"]),
                            int(header["length"])).result()
                        _send_frame(conn, {"ok": True}, bytes(data))
                    elif op == "release":
                        self._ep.submit(
                            "release",
                            _block_from_wire(header["block"])).result()
                        _send_frame(conn, {"ok": True})
                    else:
                        _send_frame(conn, {"ok": False,
                                           "error": f"bad op {op}"})
                except Hangup:
                    # fault injection: drop the connection mid-protocol
                    break
                except Exception as e:  # noqa: BLE001 - wire errors back
                    _send_frame(conn, {"ok": False, "error": str(e)})
        except (ConnectionError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self):
        self._closed = True
        # close() alone leaves a thread blocked in accept() holding the
        # kernel's socket open: a peer would still connect, and wait out
        # its whole request timeout for an answer nobody sends
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        with self._lock:
            for c in self._conns:
                try:
                    c.close()
                except OSError:
                    pass
            self._conns.clear()
        self._ep.shutdown()


class TcpConnection(Connection):
    """Client endpoint for one peer server; request/response pairs are
    serialized under a lock (one socket, in-order protocol).

    Transient transport faults (a slow peer's timeout, a dropped
    connection) retry with bounded exponential backoff — the failing
    round trip already dropped the socket, so each retry is also the
    one reconnect. Only after the retry budget (or the caller's
    timeout window) is exhausted does the error surface as a fetch
    failure and cost a whole stage re-run
    (RapidsShuffleIterator.scala:242-300 keeps that escalation)."""

    #: bounded transient-fault retries per request (first backoff
    #: _RETRY_BASE_S, doubling; total added wait stays well under any
    #: sane request timeout). The process-wide default comes from the
    #: retry policy (rapids.tpu.shuffle.retry.maxReconnects); this
    #: class attribute is the policy's own fallback.
    MAX_TRANSIENT_RETRIES = 3
    _RETRY_BASE_S = 0.05

    def __init__(self, host: str, port: int,
                 connect_timeout: float = 10.0,
                 max_transient_retries: Optional[int] = None):
        self._addr = (host, port)
        self._sock: Optional[socket.socket] = None
        self._lock = lockorder.make_lock("shuffle.tcp.client")
        self._connect_timeout = connect_timeout
        self._max_retries = _retry_policy["max_reconnects"] \
            if max_transient_retries is None else max_transient_retries
        self._jitter_s = _retry_policy["jitter_ms"] / 1e3

    def _ensure(self, timeout: float) -> socket.socket:
        if self._sock is None:
            try:
                self._sock = socket.create_connection(
                    self._addr, timeout=self._connect_timeout)
            except OSError as e:
                raise TransportError(
                    f"connect to {self._addr} failed: {e}")
        self._sock.settimeout(timeout)
        return self._sock

    def _roundtrip(self, header: dict, timeout: float):
        from spark_rapids_tpu.shuffle import fault_injection

        with self._lock:
            injector = fault_injection.get_injector()
            if injector.should_partition_dcn():
                self._drop()
                raise TransportError(
                    f"transport to {self._addr} failed: injected DCN "
                    f"partition (inter-host link down)")
            if injector.should_drop():
                self._drop()
                raise TransportError(
                    f"transport to {self._addr} failed: injected "
                    f"connection drop")
            sock = self._ensure(timeout)
            try:
                _send_frame(sock, header)
                resp, payload = _recv_frame(sock)
            except (ConnectionError, OSError, socket.timeout) as e:
                self._drop()
                raise TransportError(
                    f"transport to {self._addr} failed: {e}")
        if not resp.get("ok"):
            # peer answered with a semantic error: retrying would just
            # re-ask the same question
            raise TransportError(resp.get("error", "unknown peer error"),
                                 retryable=False)
        return resp, payload

    def _roundtrip_retrying(self, header: dict, timeout: float):
        """``_roundtrip`` with bounded exponential backoff on transient
        TransportError. The total wall time (tries + sleeps) is capped
        at the caller's ``timeout`` — a hiccuping peer costs backoff,
        never more than the budget the caller already signed up for.
        Each sleep carries uniform jitter (shuffle.retry.jitterMs) so
        the fan-in after a DCN blip — every surviving host re-knocking
        on the same peer — de-synchronizes instead of stampeding."""
        deadline = time.monotonic() + timeout
        backoff = self._RETRY_BASE_S
        attempt = 0
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TransportError(
                    f"transport to {self._addr} timed out after "
                    f"{attempt} attempts within {timeout}s")
            try:
                return self._roundtrip(header, remaining)
            except TransportError as e:
                attempt += 1
                remaining = deadline - time.monotonic()
                if not getattr(e, "retryable", True) or \
                        attempt > self._max_retries or \
                        remaining <= backoff:
                    raise
                # the failed roundtrip dropped the socket; the sleep
                # then _ensure() is the backoff + reconnect
                sleep = backoff
                if self._jitter_s:
                    sleep += random.uniform(0.0, self._jitter_s)
                time.sleep(min(sleep, remaining))
                backoff *= 2

    def _drop(self):
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    # -- Connection API ----------------------------------------------------

    def request_metadata(self, blocks: List[BlockId], timeout: float = 30.0
                         ) -> List[ShuffleTableMeta]:
        resp, _ = self._roundtrip_retrying(
            {"op": "metadata",
             "blocks": [_block_to_wire(b) for b in blocks]}, timeout)
        return [ShuffleTableMeta.from_json(m) for m in resp["metas"]]

    def request_chunk(self, block: BlockId, offset: int, length: int,
                      timeout: float = 30.0) -> bytes:
        from spark_rapids_tpu.shuffle import fault_injection

        _, payload = self._roundtrip_retrying(
            {"op": "chunk", "block": _block_to_wire(block),
             "offset": offset, "length": length}, timeout)
        # injected truncation sits ABOVE the retry loop on purpose: the
        # client's short-chunk check then escalates straight to a fetch
        # failure, the same path a mid-transfer peer crash takes
        return fault_injection.get_injector().maybe_truncate(payload)

    def release(self, block: BlockId) -> None:
        try:
            self._roundtrip({"op": "release",
                             "block": _block_to_wire(block)}, 30.0)
        except TransportError:
            pass  # best-effort: server GC also drops payload caches

    def close(self):
        with self._lock:
            self._drop()


class TcpTransport:
    """Endpoint registry over real sockets (UCXShuffleTransport's role:
    management-port bootstrap + per-peer endpoint table)."""

    def __init__(self):
        self._servers: Dict[str, TcpShuffleServer] = {}
        self._addrs: Dict[str, tuple] = {}
        self._lock = lockorder.make_lock("shuffle.tcp.registry")

    def register(self, server: ShuffleServer, host: str = "127.0.0.1",
                 port: int = 0) -> TcpShuffleServer:
        ts = TcpShuffleServer(server, host, port)
        with self._lock:
            self._servers[server.executor_id] = ts
            self._addrs[server.executor_id] = ts.address
        return ts

    def register_remote(self, executor_id: str, host: str,
                        port: int) -> None:
        """Record a peer served by ANOTHER process (the map-status
        topology info)."""
        with self._lock:
            self._addrs[executor_id] = (host, port)

    def connect(self, peer_executor_id: str) -> TcpConnection:
        with self._lock:
            addr = self._addrs.get(peer_executor_id)
        if addr is None:
            raise TransportError(f"no endpoint for {peer_executor_id}")
        return TcpConnection(*addr)

    def shutdown(self):
        with self._lock:
            for s in self._servers.values():
                s.close()
            self._servers.clear()
            self._addrs.clear()
