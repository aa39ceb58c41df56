"""RPC layer: msgpack-RPC over TCP with byte-prefix protocol demux.

Reference behavior: nomad/rpc.go — a single TCP port serves every protocol,
demuxed by the first byte (rpc.go:23-30: rpcNomad=0x01, rpcRaft=0x02,
rpcMultiplex=0x03, rpcTLS=0x04); net/rpc with a msgpack codec
(rpc.go:59-67); ``forward`` routes calls to the cluster leader or a remote
region (rpc.go:178-283); ConnPool reuses connections (nomad/pool.go).

Frame format on the Nomad channel: length-prefixed msgpack arrays
``[seq, method, body]`` for requests and ``[seq, error, body]`` for
responses — the moral of net/rpc's request/response header pairs.  The Raft
channel carries the same framing but is dispatched to the consensus layer
(raft_rpc.go RaftLayer).
"""

from __future__ import annotations

import dataclasses
import logging
import socket
import socketserver
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import msgpack

from .. import codec, fault
from ..utils import tracing
from ..utils.telemetry import NULL_TELEMETRY

# Protocol bytes (rpc.go:23-30)
RPC_NOMAD = 0x01
RPC_RAFT = 0x02
# Struct-codec channel (ISSUE 11): same [seq, method, body] envelopes,
# but frames may carry the generated flat binary layout (codec.MAGIC
# per-frame tag) instead of reflection msgpack.  Dialers handshake —
# the server acks with its codec version + schema fingerprint — and
# negotiate DOWN per connection: an old peer closes on the unknown
# protocol byte and the dialer redials the legacy channel; a peer on a
# different schema keeps the connection but sends msgpack frames (every
# receiver sniffs per frame).
RPC_NOMAD_CODEC = 0x05

_LEN = struct.Struct("<I")


class RPCError(Exception):
    pass


class TransportError(RPCError):
    """Connection-level failure (dial/read/write) — unlike an application
    error reply from the remote."""


class DialError(TransportError):
    """The connection could not even be established: the request was never
    sent, so retrying elsewhere cannot double-apply it."""


class NoLeaderError(RPCError):
    pass


class NoPathToRegion(RPCError):
    """Cross-region forwarding exhausted its bounded dial rounds: every
    known server of the target region was unreachable at DIAL time (so
    nothing was ever sent and nothing can have double-applied).  Typed
    so callers can tell "region unreachable" from "no leader": it
    carries the target ``region`` and a ``retry_after`` hint, the HTTP
    layer maps it to 429 + Retry-After, and the RPC layer re-types it
    from the wire error string — a down region degrades to a retryable
    error, never a hang."""

    def __init__(self, region: str, retry_after: float, rounds: int = 0,
                 detail: str = ""):
        self.region = region
        self.retry_after = retry_after
        self.rounds = rounds
        super().__init__(
            f"no path to region '{region}' after {rounds} dial rounds"
            + (f" ({detail})" if detail else "")
            + f"; retry_after={retry_after:.2f}")

    @staticmethod
    def from_message(msg: str) -> "NoPathToRegion":
        """Rebuild from the wire error string (the server encodes
        errors as '<TypeName>: <message>')."""
        import re

        m = re.search(r"region '([^']*)'", msg)
        region = m.group(1) if m else ""
        m = re.search(r"retry_after=([0-9.]+)", msg)
        retry = float(m.group(1)) if m else 1.0
        m = re.search(r"after (\d+) dial rounds", msg)
        rounds = int(m.group(1)) if m else 0
        return NoPathToRegion(region, retry, rounds=rounds)


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------


def _wire_default(v: Any) -> Any:
    """msgpack ``default`` hook: hot endpoints hand the frame layer RAW
    dataclasses; on a legacy (msgpack) connection they serialize to the
    exact CamelCase wire trees old peers already speak."""
    from ..api.codec import to_wire

    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return to_wire(v)
    if getattr(v, "__lazy_strs__", False):
        return list(v)
    raise TypeError(f"unserializable rpc value {type(v).__name__}")


def _pack_frame(obj: Any, binary: bool) -> bytes:
    """One frame payload: the generated struct codec when the
    connection negotiated it (falling back per frame on schema drift),
    reflection msgpack otherwise.  Both sides of every connection sniff
    the per-frame tag, so mixed frames on one stream are fine."""
    if binary and codec.enabled():
        try:
            return codec.encode(obj, "rpc")
        except codec.CodecError:
            pass  # fallback counted by codec.encode
    t0 = time.monotonic()
    data = msgpack.packb(obj, use_bin_type=True, default=_wire_default)
    codec.note_msgpack("rpc", "encode", t0, len(data))
    return data


def _unpack_frame(data: bytes) -> Tuple[Any, bool]:
    """Decode one frame payload, sniffing the per-frame codec tag.
    Returns (obj, was_binary).  A malformed codec frame surfaces as
    TransportError like any other desynchronized stream."""
    if codec.is_frame(data):
        try:
            return codec.decode(data, "rpc"), True
        except codec.CodecError as e:
            raise TransportError(f"bad codec frame: {e}") from e
    t0 = time.monotonic()
    obj = msgpack.unpackb(data, raw=False)
    codec.note_msgpack("rpc", "decode", t0, len(data))
    return obj, False


def _send_frame(sock: socket.socket, obj: Any,
                binary: bool = False) -> None:
    data = _pack_frame(obj, binary)
    act = fault.faultpoint("rpc.send")
    if act is not None:
        if act.kind == "drop":
            return  # frame lost on the wire; the peer's read times out
        if act.kind == "delay":
            time.sleep(act.delay)
        elif act.kind == "dup":
            sock.sendall(_LEN.pack(len(data)) + data)
        elif act.kind == "truncate":
            # Ship the length prefix + a partial payload, then sever the
            # connection: the peer reads EOF mid-frame (the torn-write
            # shape _recv_exact must surface as TransportError).
            cut = max(1, len(data) // 2)
            sock.sendall(_LEN.pack(len(data)) + data[:cut])
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            sock.close()
            raise ConnectionError(act.message)
        elif act.kind in ("error", "crash"):
            # Surface as the transport failure a real broken wire raises,
            # so the fault exercises the SAME classify/discard/retry
            # machinery production errors take (ConnPool wraps this into
            # TransportError; RemoteServerRPC demotes and retries).
            raise ConnectionError(act.message)
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            # EOF mid-frame is a transport failure, not a decode problem:
            # surfacing it as TransportError (with how much arrived) keeps
            # a truncated frame from propagating as a confusing
            # struct/msgpack error further up.
            if buf:
                raise TransportError(
                    f"connection closed mid-frame ({len(buf)}/{n} bytes)")
            raise TransportError("connection closed")
        buf += chunk
    return buf


def _recv_frame_tagged(sock: socket.socket) -> Tuple[Any, bool]:
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if n > 64 << 20:
        # A ludicrous length prefix means the stream is desynchronized
        # (or hostile): transport-level, the connection must be discarded.
        raise TransportError(f"frame too large: {n}")
    return _unpack_frame(_recv_exact(sock, n))


def _recv_frame(sock: socket.socket) -> Any:
    return _recv_frame_tagged(sock)[0]


# ---------------------------------------------------------------------------
# server side
# ---------------------------------------------------------------------------


class RPCServer:
    """TCP listener demuxing Nomad-RPC and Raft channels onto handlers.

    ``register(method, fn)`` exposes ``fn(body) -> reply`` on the Nomad
    channel; ``raft_handler`` receives raft messages (election/replication)
    from peers.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 logger: Optional[logging.Logger] = None,
                 tls_context=None, metrics=None):
        self.logger = logger or logging.getLogger("nomad_tpu.rpc")
        self.metrics = metrics if metrics is not None else NULL_TELEMETRY
        self.methods: Dict[str, Callable[[Any], Any]] = {}
        self.raft_handler: Optional[Callable[[Any], Any]] = None
        self.tls_context = tls_context
        outer = self

        self._active: set = set()
        self._active_lock = threading.Lock()

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                sock = self.request
                # Track the RAW socket first so shutdown() can sever a
                # connection stuck mid-handshake; bound the handshake so a
                # silent peer cannot pin this thread forever.
                with outer._active_lock:
                    outer._active.add(sock)
                if outer.tls_context is not None:
                    # mTLS: every connection handshakes before the
                    # protocol byte (helper/tlsutil wraps the whole
                    # stream; rpcTLS demux byte in the reference).
                    try:
                        sock.settimeout(10.0)
                        tls_sock = outer.tls_context.wrap_socket(
                            sock, server_side=True)
                        tls_sock.settimeout(None)
                    except OSError as e:
                        outer.logger.warning("rpc: TLS handshake failed: %s",
                                             e)
                        with outer._active_lock:
                            outer._active.discard(sock)
                        return
                    with outer._active_lock:
                        outer._active.discard(sock)
                        outer._active.add(tls_sock)
                    sock = tls_sock
                try:
                    try:
                        prefix = _recv_exact(sock, 1)[0]
                    except (TransportError, ConnectionError, OSError):
                        return
                    if prefix == RPC_NOMAD:
                        outer._serve_nomad(sock)
                    elif prefix == RPC_RAFT:
                        outer._serve_raft(sock)
                    elif prefix == RPC_NOMAD_CODEC and codec.enabled():
                        # Handshake ack: magic + version + schema
                        # fingerprint.  The dialer compares fingerprints
                        # and falls back to msgpack FRAMES on mismatch
                        # (the channel still serves: every frame is
                        # sniffed).
                        try:
                            sock.sendall(bytes((codec.MAGIC,
                                                codec.VERSION))
                                         + codec.FINGERPRINT)
                        except OSError:
                            return
                        outer._serve_nomad(sock)
                    else:
                        # Unknown byte — including the codec channel
                        # under NOMAD_TPU_CODEC=0 (an old msgpack-only
                        # build behaves identically): close, and the
                        # dialer negotiates down to the legacy channel.
                        outer.logger.warning(
                            "rpc: unrecognized protocol byte %#x", prefix)
                finally:
                    with outer._active_lock:
                        outer._active.discard(sock)
                        outer._active.discard(self.request)

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self.tcp = Server((host, port), Handler)
        self.host = host
        self.port = self.tcp.server_address[1]
        self._thread = threading.Thread(target=self.tcp.serve_forever,
                                        name="rpc", daemon=True)

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    def start(self) -> None:
        self._thread.start()

    def shutdown(self) -> None:
        self.tcp.shutdown()
        self.tcp.server_close()
        # Established connections must die with the server: a peer's pooled
        # connection left open would keep talking to this dead instance's
        # in-memory state instead of reconnecting to its successor.
        with self._active_lock:
            conns = list(self._active)
            self._active.clear()
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass

    def register(self, method: str, fn: Callable[[Any], Any]) -> None:
        self.methods[method] = fn

    def _serve_nomad(self, sock: socket.socket) -> None:
        """One connection, many sequential requests (like a net/rpc codec
        session over a pooled yamux stream)."""
        while True:
            try:
                (seq, method, body), req_binary = _recv_frame_tagged(sock)
            except (TransportError, ConnectionError, OSError, ValueError):
                return
            self.metrics.incr_counter("rpc.request")
            if not req_binary:
                # Per-method msgpack-frame accounting (ISSUE 12
                # satellite): the residual reflection traffic must be
                # provably Status/Serf control chatter, never a hot
                # scheduling method — codec.msgpack_methods() is the
                # profile the soak report reads.
                codec.note_msgpack_method(method)
            fn = self.methods.get(method)
            if fn is None:
                # Unknown methods are rejected traffic, not silence.
                self.metrics.incr_counter("rpc.request_error")
                reply = [seq, f"rpc: can't find method {method}", None]
            else:
                t0 = time.perf_counter()
                # Branch before building the span attrs: the disarmed
                # per-request path pays one load + comparison only.
                tr = tracing.TRACER
                req_span = tracing.NOOP if tr is None else tr.span(
                    "rpc.request", method=method)
                try:
                    with req_span:
                        reply = [seq, None, fn(body)]
                except NoLeaderError as e:
                    reply = [seq, f"__no_leader__:{e}", None]
                except Exception as e:  # error string back to caller
                    self.metrics.incr_counter("rpc.request_error")
                    reply = [seq, f"{type(e).__name__}: {e}", None]
                self.metrics.measure_since(f"rpc.request.{method}", t0)
            try:
                # Reply in the codec the request arrived in: the peer
                # chose it at handshake (or per frame on schema drift).
                _send_frame(sock, reply, binary=req_binary)
            except (ConnectionError, OSError):
                return

    def _serve_raft(self, sock: socket.socket) -> None:
        while True:
            try:
                seq, _method, body = _recv_frame(sock)
            except (TransportError, ConnectionError, OSError, ValueError):
                return
            handler = self.raft_handler
            if handler is None:
                reply = [seq, "raft: not ready", None]
            else:
                try:
                    reply = [seq, None, handler(body)]
                except Exception as e:
                    reply = [seq, f"{type(e).__name__}: {e}", None]
            try:
                _send_frame(sock, reply)
            except (ConnectionError, OSError):
                return


# ---------------------------------------------------------------------------
# client side / conn pool (nomad/pool.go)
# ---------------------------------------------------------------------------


class _HandshakeRefused(Exception):
    """The peer closed on the codec protocol byte: an old msgpack-only
    build (or NOMAD_TPU_CODEC=0).  The pool negotiates the ADDRESS down
    to the legacy channel and redials."""


class _Conn:
    def __init__(self, addr: str, channel: int, timeout: float,
                 tls_context=None):
        host, port = addr.rsplit(":", 1)
        self.sock = socket.create_connection((host, int(port)),
                                             timeout=timeout)
        if tls_context is not None:
            self.sock = tls_context.wrap_socket(self.sock,
                                                server_hostname=host)
        self.sock.sendall(bytes([channel]))
        self.binary = False
        if channel == RPC_NOMAD_CODEC:
            # Codec handshake: ack = magic + version + 8-byte schema
            # fingerprint.  A clean EOF here is the old-peer signature
            # (it reads the unknown protocol byte and orderly-closes) →
            # _HandshakeRefused, and the pool pins the ADDRESS to the
            # legacy channel.  Timeouts and resets are NOT refusals — a
            # restarting or GIL-stalled codec peer must not get
            # demoted to msgpack for the process lifetime — they
            # surface as dial failures and the next dial re-probes.  A
            # fingerprint/version mismatch keeps the connection but
            # pins it to msgpack frames: flat layouts are only spoken
            # between peers PROVEN to share the schema.
            try:
                self.sock.settimeout(timeout)
                ack = _recv_exact(self.sock, 2 + len(codec.FINGERPRINT))
            except TransportError as e:
                try:
                    self.sock.close()
                except OSError:
                    pass
                if "mid-frame" in str(e):
                    # Partial ack then EOF: the peer was mid-crash, not
                    # refusing the protocol — don't mark legacy.
                    raise ConnectionError(
                        f"codec handshake torn: {e}") from e
                raise _HandshakeRefused(str(e)) from e
            except (ConnectionError, OSError) as e:
                # Reset / timeout: transient transport failure.
                try:
                    self.sock.close()
                except OSError:
                    pass
                raise
            self.binary = (ack[0] == codec.MAGIC
                           and ack[1] == codec.VERSION
                           and ack[2:] == codec.FINGERPRINT)
        self.seq = 0
        self.lock = threading.Lock()

    def call(self, method: str, body: Any, timeout: float) -> Any:
        with self.lock:
            self.seq += 1
            seq = self.seq
            self.sock.settimeout(timeout)
            _send_frame(self.sock, [seq, method, body],
                        binary=self.binary)
            rseq, err, reply = _recv_frame(self.sock)
        if rseq != seq:
            # Desynchronized stream — the connection is unusable.
            raise ConnectionError(f"rpc: sequence mismatch ({rseq} != {seq})")
        if err:
            if isinstance(err, str) and err.startswith("__no_leader__:"):
                raise NoLeaderError(err.split(":", 1)[1])
            if isinstance(err, str) and err.startswith("BrokerLimitError"):
                # Re-type the admission NACK so wire callers get the
                # retry_after hint instead of a generic RPCError (the
                # client's jittered-backoff retry plumbing keys on it).
                from .eval_broker import BrokerLimitError

                raise BrokerLimitError.from_message(err)
            if isinstance(err, str) and err.startswith("NoPathToRegion"):
                # A remote server's cross-region forward exhausted its
                # dial rounds — re-type so the caller sees the target
                # region and retry_after hint rather than a bare string.
                raise NoPathToRegion.from_message(err)
            raise RPCError(err)
        return reply

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


class ConnPool:
    """Connection reuse per (addr, channel) (pool.go:144).

    Hands out *parallel* connections: a call checks out an idle connection
    (or dials a new one) and returns it afterwards, so a long-poll holding
    one connection cannot starve short calls — the role yamux stream
    multiplexing plays in the reference (pool.go getClient + yamux
    Session.Open).

    Chaos surface (ISSUE 12): every call passes the ``net.send`` fault
    point and every fresh dial the ``net.dial`` point, carrying
    ``(local_addr, addr)`` so named partition groups and asymmetric
    src/dst rules apply — this single seam covers the Nomad channel AND
    the MultiRaft replication transport (raft replication rides
    ``pool.call`` too).  ``local_addr`` is stamped by the owning Server
    with its advertised address; pools without an identity (clients)
    match only ``*`` patterns.  ``chaos_exempt`` pools bypass the plane
    entirely — the harness's control/audit channel, which must reach a
    "partitioned" server the way an out-of-band console would.
    """

    MAX_IDLE_PER_KEY = 4
    # Per-address dial backoff (redial-storm fix): a dead peer's
    # dials fail instantly (connection refused), so every retry round —
    # replicators, elections, RemoteServerRPC walks — used to hammer it
    # with a fresh socket.  Failures now arm a shared jittered Backoff
    # per address; while it holds, dials fail fast LOCALLY (DialError,
    # no socket) and the cap bounds how stale the gate can get.
    DIAL_BACKOFF_BASE = 0.05
    DIAL_BACKOFF_MAX = 2.0

    def __init__(self, timeout: float = 10.0, tls_context=None):
        self.timeout = timeout
        self.tls_context = tls_context
        self.local_addr = ""       # stamped by the owning Server
        self.chaos_exempt = False  # control/audit pools bypass the plane
        self._idle: Dict[Tuple[str, int], List[_Conn]] = {}
        self._lock = threading.Lock()
        # Addresses that refused the codec handshake (old builds /
        # kill-switched peers): remembered so every later dial goes
        # straight to the legacy channel — per-connection negotiation,
        # paid once per address.
        self._legacy_addrs: set = set()
        # addr -> (Backoff, not_before_monotonic)
        self._dial_gate: Dict[str, list] = {}

    def _net_check(self, kind: str, addr: str) -> None:
        """Partition/rule verdict for one dial or call.  Blocked traffic
        surfaces as DialError: the request was never sent, so every
        retry path may safely go elsewhere (the same guarantee a real
        unreachable peer gives)."""
        if self.chaos_exempt:
            return
        act = fault.netpoint(kind, self.local_addr, addr)
        if act is None:
            return
        action, delay = act
        if action == "drop":
            raise DialError(
                f"rpc to {addr} failed: network partitioned (injected)")
        if delay > 0:
            time.sleep(delay)

    def _dial(self, addr: str, channel: int, timeout: float) -> _Conn:
        self._net_check("dial", addr)
        now = time.monotonic()
        with self._lock:
            gate = self._dial_gate.get(addr)
            if gate is not None and now < gate[1]:
                raise DialError(
                    f"rpc to {addr} failed: in dial backoff for another "
                    f"{gate[1] - now:.2f}s after {gate[0].attempt} "
                    "consecutive dial failures")
        try:
            conn = self._dial_raw(addr, channel, timeout)
        except OSError:
            from ..utils.backoff import Backoff
            with self._lock:
                gate = self._dial_gate.get(addr)
                if gate is None:
                    gate = [Backoff(base=self.DIAL_BACKOFF_BASE,
                                    max_delay=self.DIAL_BACKOFF_MAX), 0.0]
                    self._dial_gate[addr] = gate
                gate[1] = time.monotonic() + gate[0].next_delay()
            raise
        with self._lock:
            self._dial_gate.pop(addr, None)
        return conn

    def _dial_raw(self, addr: str, channel: int, timeout: float) -> _Conn:
        if (channel == RPC_NOMAD and codec.enabled()
                and addr not in self._legacy_addrs):
            try:
                return _Conn(addr, RPC_NOMAD_CODEC, timeout,
                             tls_context=self.tls_context)
            except _HandshakeRefused as e:
                # Orderly refusal = old build / kill-switched peer.
                # Visible: operators should be able to tell a
                # negotiated-down fleet from a codec one.
                logging.getLogger("nomad_tpu.rpc").info(
                    "rpc: %s refused the codec channel (%s); pinning "
                    "legacy msgpack for this address", addr, e)
                codec.TELEMETRY.incr_counter("codec.negotiate_down")
                with self._lock:
                    self._legacy_addrs.add(addr)
        return _Conn(addr, channel, timeout,
                     tls_context=self.tls_context)

    def call(self, addr: str, method: str, body: Any,
             channel: int = RPC_NOMAD, timeout: Optional[float] = None) -> Any:
        timeout = timeout if timeout is not None else self.timeout
        self._net_check("send", addr)
        key = (addr, channel)
        with self._lock:
            bucket = self._idle.get(key)
            conn = bucket.pop() if bucket else None
        if conn is None:
            try:
                conn = self._dial(addr, channel, timeout)
            except OSError as e:  # includes ssl.SSLError
                raise DialError(f"rpc to {addr} failed: {e}") from e
        try:
            reply = conn.call(method, body, timeout)
        except TransportError:
            # Already classified (EOF mid-frame, oversized/desynced
            # frame): the socket is poisoned — discard, never re-pool.
            conn.close()
            raise
        except (ConnectionError, OSError) as e:
            # Includes socket.timeout: a reply may still be in flight, so
            # releasing this connection would hand the NEXT caller a stale
            # response (sequence mismatch at best).  Discard.
            conn.close()
            raise TransportError(f"rpc to {addr} failed: {e}") from e
        except RPCError:
            # Application-level error reply: the transport is still healthy,
            # keep the connection pooled.
            self._release(key, conn)
            raise
        self._release(key, conn)
        return reply

    def _release(self, key: Tuple[str, int], conn: _Conn) -> None:
        with self._lock:
            bucket = self._idle.setdefault(key, [])
            if len(bucket) < self.MAX_IDLE_PER_KEY:
                bucket.append(conn)
                return
        conn.close()

    def invalidate(self, addr: str) -> None:
        """Drop every idle connection to ``addr`` (all channels), clear
        its dial gate, and un-pin any legacy-msgpack demotion: a peer
        KNOWN to have restarted leaves only dead sockets in the pool
        (draining them one TransportError at a time wastes a failed
        call per conn), and a zero-byte EOF its death raced into the
        codec handshake must not demote its codec-capable successor to
        msgpack for the pool's lifetime — the next dial re-probes."""
        with self._lock:
            dead = [conn for key, bucket in self._idle.items()
                    if key[0] == addr for conn in bucket]
            for key in [k for k in self._idle if k[0] == addr]:
                del self._idle[key]
            self._dial_gate.pop(addr, None)
            self._legacy_addrs.discard(addr)
        for conn in dead:
            conn.close()

    def close(self) -> None:
        with self._lock:
            for bucket in self._idle.values():
                for conn in bucket:
                    conn.close()
            self._idle.clear()


# ---------------------------------------------------------------------------
# client agent -> server RPC adapter
# ---------------------------------------------------------------------------


class RemoteServerRPC:
    """The duck-typed RPC surface nomad_tpu.client.Client expects
    (node_register / node_update_status / node_get_client_allocs /
    node_update_allocs), carried over the wire to a server — what the
    reference client does via msgpack-RPC (client/rpc via
    client.go:465 Client.RPC).

    Retries across the server list with bounded rounds and jittered
    exponential backoff between them (a fleet of clients whose leader
    died must not re-dial in lockstep).  A ``NoLeaderError`` reply
    carries the responding follower's best-known leader address — that
    server is promoted to the front of the list so the next attempt goes
    straight at the leader instead of re-walking stale entries.
    """

    MAX_ROUNDS = 3

    def __init__(self, servers: List[str], pool: Optional[ConnPool] = None,
                 max_rounds: Optional[int] = None, sleep=time.sleep):
        from ..api.codec import ensure
        from ..utils.backoff import Backoff
        self._ensure = ensure
        self.servers = list(servers)
        self.pool = pool or ConnPool()
        self.max_rounds = max_rounds or self.MAX_ROUNDS
        self._sleep = sleep
        self._backoff_factory = lambda: Backoff(base=0.05, max_delay=2.0)

    @staticmethod
    def _looks_like_addr(hint: str) -> bool:
        """A NoLeaderError message is only a usable leader hint when it is
        an actual host:port — during elections servers reply with prose
        ('no cluster leader', 'not the leader'), and promoting that into
        the server list would poison every later dial."""
        host, sep, port = hint.rpartition(":")
        return bool(sep) and bool(host) and port.isdigit()

    def _promote(self, addr: str) -> None:
        if addr in self.servers:
            self.servers.remove(addr)
        self.servers.insert(0, addr)

    def _demote(self, addr: str) -> None:
        if addr in self.servers:
            self.servers.remove(addr)
            self.servers.append(addr)

    def _call(self, method: str, body: Any) -> Any:
        last: Optional[Exception] = None
        bo = self._backoff_factory()
        for round_no in range(self.max_rounds):
            if round_no:
                self._sleep(bo.next_delay())
            for addr in list(self.servers):
                try:
                    return self.pool.call(addr, method, body)
                except NoLeaderError as e:
                    # The server answered but isn't leader: re-resolve.
                    # Its reply names the leader when it knows one — aim
                    # the next attempt there rather than round-robining.
                    last = e
                    leader = str(e).strip()
                    if (leader != addr and self._looks_like_addr(leader)):
                        self._promote(leader)
                        break  # restart the scan at the leader
                    self._demote(addr)
                except (RPCError, OSError) as e:
                    last = e
                    self._demote(addr)
        raise RPCError(
            f"no servers reachable after {self.max_rounds} rounds: {last}")

    def node_register(self, node):
        # Bodies carry RAW dataclasses: the frame layer encodes them
        # with the struct codec on negotiated connections and converts
        # to the CamelCase wire trees for legacy msgpack peers.
        reply = self._call("Node.Register", {"Node": node})
        return reply["Index"], reply["HeartbeatTTL"]

    def node_update_status(self, node_id: str, status: str):
        reply = self._call("Node.UpdateStatus",
                           {"NodeID": node_id, "Status": status})
        return reply["Index"], reply["HeartbeatTTL"]

    def node_get_client_allocs(self, node_id: str, min_index: int = 0,
                               max_wait: float = 30.0):
        from ..structs import structs as s
        reply = self._call("Node.GetClientAllocs",
                           {"NodeID": node_id, "MinQueryIndex": min_index,
                            "MaxQueryTime": max_wait})
        allocs = [self._ensure(s.Allocation, a)
                  for a in reply["Allocs"] or []]
        return allocs, reply["Index"]

    def node_update_allocs(self, allocs):
        reply = self._call("Node.UpdateAlloc", {"Allocs": list(allocs)})
        return reply["Index"]

    def node_get(self, node_id: str):
        from ..structs import structs as s
        reply = self._call("Node.Get", {"NodeID": node_id})
        data = reply.get("Node")
        return self._ensure(s.Node, data) if data else None

    def alloc_get(self, alloc_id: str):
        from ..structs import structs as s
        reply = self._call("Alloc.Get", {"AllocID": alloc_id})
        data = reply.get("Alloc")
        return self._ensure(s.Allocation, data) if data else None

    def derive_vault_token(self, alloc_id: str, task_names):
        reply = self._call("Node.DeriveVaultToken",
                           {"AllocID": alloc_id, "Tasks": list(task_names)})
        return reply["Tasks"]
