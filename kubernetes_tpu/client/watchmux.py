"""Selector-driven HTTP watch multiplexer — thousands of watch streams
on a handful of threads.

The thread-per-stream cost of :meth:`RestClient.watch` caps a fleet
harness at a few hundred informers; real fleets run tens of thousands.
:class:`HttpWatchMux` drives every stream off a small pool of
``selectors`` event loops: each stream is a non-blocking socket
speaking the server's chunked newline-JSON watch protocol, parsed
incrementally (status line → headers → chunk framing → event lines)
with no thread parked on any one of them.

Failover is the reflector contract spread across replicas: a dropped
socket (replica killed, mid-frame disconnect, write-deadline close)
reconnects to the NEXT url in the replica list from the highest rv
delivered — the shared event ring replays the gap.  A 410/Expired
answer (rv fell out of the ring) triggers a relist through
:class:`RestClient` and a fresh watch from the list's rv; the cache is
rebuilt and the rv audit resets for the new stream segment, exactly as
a reflector's does.

:class:`MuxInformer` is the per-stream cache + audit.  The audit
checks the ordering the sharded store actually guarantees: rv strictly
increasing PER NAMESPACE (a namespace maps to one store shard, and
each shard's fan-out delivers in ascending commit order — events of
one kind on DIFFERENT shards may legitimately interleave, see
api/store.py's watch-path notes).  ``violations`` stays empty iff no
namespace ever saw rv go backwards within a segment — including
across a replica failover, which is what the serving chaos family
asserts (tests/test_chaos.py SERVING_SEEDS)."""

from __future__ import annotations

import errno
import json
import selectors
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import urlsplit

from ..api import store as st
from ..api import wire
from .rest import RestClient

# stream states
_CONNECTING = "connecting"
_SENDING = "sending"
_HEADERS = "headers"
_STREAMING = "streaming"
_CLOSED = "closed"


class _ChunkDecoder:
    """Incremental HTTP/1.1 chunked-transfer decoder.  Feed raw bytes,
    read back payload bytes; flags the terminal 0-chunk (the server
    ended the stream — the client must relist-and-rewatch, same as
    RestClient.watch's trailing Expired)."""

    def __init__(self) -> None:
        self.buf = bytearray()
        self.left = 0  # >0: bytes left in chunk; -2: eat trailing CRLF
        self.eof = False

    def feed(self, data: bytes) -> bytes:
        self.buf += data
        out = bytearray()
        while not self.eof:
            if self.left > 0:
                take = min(self.left, len(self.buf))
                if not take:
                    break
                out += self.buf[:take]
                del self.buf[:take]
                self.left -= take
                if self.left == 0:
                    self.left = -2
            elif self.left == -2:
                if len(self.buf) < 2:
                    break
                del self.buf[:2]
                self.left = 0
            else:
                i = self.buf.find(b"\r\n")
                if i < 0:
                    break
                size = int(bytes(self.buf[:i]).split(b";")[0] or b"0", 16)
                del self.buf[: i + 2]
                if size == 0:
                    self.eof = True
                    break
                self.left = size
        return bytes(out)


class MuxInformer:
    """Cache + audit for one multiplexed watch stream.

    ``on_event(typ, obj, rv, recv_ts)`` fires for every non-bookmark
    event after the cache applies it — the harness hooks it to compute
    watch-delivery latency against the commit-time table.  ``last_rv``
    is the resume cursor: the MAX rv delivered (cross-shard interleave
    can deliver a lower rv after a higher one; resuming must never move
    the cursor backwards).  ``violations`` collects per-namespace rv
    regressions — the ordering the store's per-shard fan-out does
    guarantee; segments reset on relist, never on plain failover."""

    def __init__(
        self,
        kind: str,
        on_event: Optional[Callable[[str, Any, int, float], None]] = None,
    ) -> None:
        self.kind = kind
        self.on_event = on_event
        self.cache: Dict[str, Any] = {}
        self.last_rv = 0
        self.events_delivered = 0
        self.bookmarks = 0
        self.relists = 0
        self.failovers = 0
        self.violations: List[str] = []
        self.synced = False
        self._ns_rv: Dict[str, int] = {}

    @staticmethod
    def _key(obj: Any) -> str:
        return f"{obj.meta.namespace}/{obj.meta.name}"

    def apply_list(self, items: List[Any], rv: int) -> None:
        self.cache = {self._key(o): o for o in items}
        self.last_rv = rv
        self._ns_rv = {}  # new segment: the audit restarts with it
        self.relists += 1
        self.synced = True

    def apply_event(self, typ: str, obj: Any, rv: int) -> None:
        ns = obj.meta.namespace
        seen = self._ns_rv.get(ns, 0)
        if rv <= seen:
            self.violations.append(
                f"{self.kind}: ns {ns!r} rv went backwards {seen} -> {rv}"
                f" ({typ} {self._key(obj)})"
            )
        self._ns_rv[ns] = max(seen, rv)
        if rv > self.last_rv:
            self.last_rv = rv
        if typ == "DELETED":
            self.cache.pop(self._key(obj), None)
        else:
            self.cache[self._key(obj)] = obj
        self.events_delivered += 1
        if self.on_event is not None:
            self.on_event(typ, obj, rv, time.monotonic())


class _Stream:
    """One non-blocking watch connection inside a mux loop."""

    def __init__(self, informer: MuxInformer, url_index: int) -> None:
        self.informer = informer
        self.url_index = url_index
        self.sock: Optional[socket.socket] = None
        self.state = _CLOSED
        self.outbuf = b""
        self.hdrbuf = bytearray()
        self.status: Optional[int] = None
        self.decoder = _ChunkDecoder()
        self.linebuf = bytearray()
        self.retry_at = 0.0  # monotonic deadline before reconnecting
        self.needs_relist = False


class _MuxLoop:
    """One selector event loop owning a partition of the streams."""

    def __init__(self, mux: "HttpWatchMux", name: str) -> None:
        self.mux = mux
        self.sel = selectors.DefaultSelector()
        self.lock = threading.Lock()
        self.pending: List[_Stream] = []
        self.thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )

    def add(self, stream: _Stream) -> None:
        with self.lock:
            self.pending.append(stream)

    def _run(self) -> None:
        mux = self.mux
        while not mux._stop.is_set():
            now = time.monotonic()
            with self.lock:
                due = [s for s in self.pending if s.retry_at <= now]
                self.pending = [
                    s for s in self.pending if s.retry_at > now
                ]
            for s in due:
                try:
                    if s.needs_relist:
                        mux._relist(s)
                    self._connect(s)
                except Exception:
                    # failed relist/connect (replica mid-restart):
                    # rotate and retry after the backoff
                    s.url_index += 1
                    self._close(s)
            events = self.sel.select(timeout=0.05)
            for key, mask in events:
                stream = key.data
                try:
                    if stream.state == _CONNECTING and (
                        mask & selectors.EVENT_WRITE
                    ):
                        self._finish_connect(stream)
                    elif stream.state == _SENDING and (
                        mask & selectors.EVENT_WRITE
                    ):
                        self._flush_request(stream)
                    elif mask & selectors.EVENT_READ:
                        self._read(stream)
                except Exception:
                    self._failover(stream)

    # -- connection lifecycle ------------------------------------------

    def _connect(self, stream: _Stream) -> None:
        host, port, _ = self.mux._target(stream)
        inf = stream.informer
        path = f"/api/v1/watch/{inf.kind}"
        if inf.synced:
            # also from rv 0: a list of an empty store reads rv 0, and a
            # watch "from now" after it would lose what is written
            # between the list and the server's taking the watch
            path += f"?from_rv={inf.last_rv}"
        req = (
            f"GET {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Accept: application/json\r\n"
        )
        if self.mux._token:
            req += f"Authorization: Bearer {self.mux._token}\r\n"
        req += "\r\n"
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setblocking(False)
        stream.sock = sock
        stream.outbuf = req.encode()
        stream.hdrbuf = bytearray()
        stream.status = None
        stream.decoder = _ChunkDecoder()
        stream.linebuf = bytearray()
        err = sock.connect_ex((host, port))
        if err in (0, errno.EISCONN):
            stream.state = _SENDING
            self.sel.register(sock, selectors.EVENT_WRITE, stream)
        elif err in (errno.EINPROGRESS, errno.EWOULDBLOCK):
            stream.state = _CONNECTING
            self.sel.register(sock, selectors.EVENT_WRITE, stream)
        else:
            raise OSError(err, "connect failed")

    def _finish_connect(self, stream: _Stream) -> None:
        err = stream.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
        if err:
            raise OSError(err, "connect failed")
        stream.state = _SENDING
        self._flush_request(stream)

    def _flush_request(self, stream: _Stream) -> None:
        while stream.outbuf:
            try:
                n = stream.sock.send(stream.outbuf)
            except (BlockingIOError, InterruptedError):
                return
            stream.outbuf = stream.outbuf[n:]
        stream.state = _HEADERS
        self.sel.modify(stream.sock, selectors.EVENT_READ, stream)

    def _read(self, stream: _Stream) -> None:
        try:
            data = stream.sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        if not data:
            # replica died or write-deadline closed us: plain failover
            # from last_rv — the ring replays the gap
            raise ConnectionResetError("stream closed by server")
        if stream.state == _HEADERS:
            stream.hdrbuf += data
            end = stream.hdrbuf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = bytes(stream.hdrbuf[:end]).decode("latin-1")
            status_line = head.split("\r\n", 1)[0]
            stream.status = int(status_line.split(" ", 2)[1])
            body = bytes(stream.hdrbuf[end + 4:])
            stream.hdrbuf = bytearray()
            if stream.status == 410:
                # rv fell out of the ring: relist, then rewatch
                stream.needs_relist = True
                raise st.Expired("watch rv expired")
            if stream.status != 200:
                raise OSError(f"watch HTTP {stream.status}")
            stream.state = _STREAMING
            data = body
            if not data:
                return
        payload = stream.decoder.feed(data)
        if payload:
            self._deliver(stream, payload)
        if stream.decoder.eof:
            # terminal chunk: the SERVER ended the stream (overflow
            # termination / shutdown) — relist-and-rewatch, the same
            # contract RestClient.watch raises Expired for
            stream.needs_relist = True
            raise st.Expired("watch stream ended by server")

    def _deliver(self, stream: _Stream, payload: bytes) -> None:
        stream.linebuf += payload
        while True:
            i = stream.linebuf.find(b"\n")
            if i < 0:
                return
            line = bytes(stream.linebuf[:i]).strip()
            del stream.linebuf[: i + 1]
            if not line:
                continue
            doc = json.loads(line)
            inf = stream.informer
            if doc["type"] == "BOOKMARK":
                inf.bookmarks += 1
                if doc["rv"] > inf.last_rv:
                    inf.last_rv = doc["rv"]
                continue
            inf.apply_event(
                doc["type"], wire.from_wire(doc["object"]), doc["rv"]
            )

    # -- failure handling ----------------------------------------------

    def _close(self, stream: _Stream, requeue: bool = True) -> None:
        if stream.sock is not None:
            try:
                self.sel.unregister(stream.sock)
            except (KeyError, ValueError):
                pass
            try:
                stream.sock.close()
            except OSError:
                pass
            stream.sock = None
        stream.state = _CLOSED
        if requeue:
            stream.retry_at = time.monotonic() + HttpWatchMux.RETRY_DELAY
            self.add(stream)

    def _failover(self, stream: _Stream) -> None:
        """Rotate to the next replica and reconnect from last_rv."""
        if stream.state == _STREAMING:
            stream.informer.failovers += 1
        stream.url_index += 1
        self._close(stream)


class HttpWatchMux:
    """Multiplex N watch streams over the replica set on a few threads.

    ``urls`` is the replica base-url list (APIServerReplicaSet.urls());
    it may be refreshed with :meth:`set_urls` after a restart swaps a
    replica onto a new port.  ``token`` rides every request the same
    way RestClient sends it.  ``threads`` selector loops split the
    streams round-robin — one loop handles hundreds of streams, but a
    thousand-informer fleet wants a few so JSON decode parallelizes
    across cores."""

    RETRY_DELAY = 0.2  # backoff before reconnecting a failed stream

    def __init__(
        self,
        urls: List[str],
        token: Optional[str] = None,
        relist_timeout: float = 10.0,
        threads: int = 4,
    ) -> None:
        if not urls:
            raise ValueError("HttpWatchMux needs at least one replica url")
        self._urls = list(urls)
        self._token = token
        self._relist_timeout = relist_timeout
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._streams: List[_Stream] = []
        self._loops = [
            _MuxLoop(self, name=f"watchmux-{i}")
            for i in range(max(1, threads))
        ]

    # -- public surface ------------------------------------------------

    def add_informer(
        self,
        kind: str,
        from_rv: Optional[int] = None,
        on_event: Optional[Callable[[str, Any, int, float], None]] = None,
    ) -> MuxInformer:
        inf = MuxInformer(kind, on_event=on_event)
        if from_rv is not None:
            inf.last_rv = from_rv
            inf.synced = True
        stream = _Stream(inf, len(self._streams) % len(self._urls))
        if from_rv is None:
            stream.needs_relist = True
        self._streams.append(stream)
        self._loops[(len(self._streams) - 1) % len(self._loops)].add(stream)
        return inf

    def set_urls(self, urls: List[str]) -> None:
        with self._lock:
            self._urls = list(urls)

    def start(self) -> None:
        for loop in self._loops:
            loop.thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        for loop in self._loops:
            if loop.thread.is_alive():
                loop.thread.join(timeout=timeout)
        for s in self._streams:
            if s.sock is not None:
                try:
                    s.sock.close()
                except OSError:
                    pass
                s.sock = None

    def informers(self) -> List[MuxInformer]:
        return [s.informer for s in self._streams]

    def violations(self) -> List[str]:
        out: List[str] = []
        for s in self._streams:
            out.extend(s.informer.violations)
        return out

    # -- loop helpers ----------------------------------------------------

    def _target(self, stream: _Stream) -> Tuple[str, int, str]:
        with self._lock:
            url = self._urls[stream.url_index % len(self._urls)]
        parts = urlsplit(url)
        return parts.hostname or "127.0.0.1", parts.port or 80, url

    def _relist(self, stream: _Stream) -> None:
        """Blocking relist through RestClient against the current
        replica.  Runs on the owning loop thread: relists are rare (rv
        outran the ring) and bounded by relist_timeout, an acceptable
        stall for the loop's partition."""
        _, _, url = self._target(stream)
        client = RestClient(
            url, timeout=self._relist_timeout, token=self._token
        )
        items, rv = client.list(stream.informer.kind)
        stream.informer.apply_list(items, rv)
        stream.needs_relist = False
