"""One writer for every chunked watch stream of an APIServer.

A chunked watch connection's handler thread sends the 200 and its headers,
then hands the stream to this writer and parks until the stream ends. The
writer is one thread (role ``watch_send``) that serves every stream:

- **ready set** — a ``Watcher``'s ready hook runs on the first send (or
  end) after the writer took that watcher's last batch. The hook adds the
  stream to the ready set and sets the writer's one wake-up: a set add
  and an event set, once a batch, never once an event.
- **one linger** — the writer wakes on the first ready stream, sleeps the
  server's ``watch_write_linger`` once, then drains every ready stream (up
  to ``MAX_ITEMS`` events each) through ``APIServer.translate_batch`` and
  writes each stream's frames as one send on that stream's socket. Fifty
  streams with events in one window cost one wake and one linger, not
  fifty. As ``Watcher.next_batch`` does, it skips the linger when a ready
  stream already holds a full batch.
- **non-blocking writes** — a socket whose kernel buffer is full keeps the
  remainder of its bytes; the writer stops draining that watcher until
  ``poll`` says the socket can take bytes again, and the watcher's own
  queue absorbs events meanwhile, so its ``lag_limit`` (coalescing, then
  drop to resync) decides its fate as it did when a thread blocked in
  ``sendall``. A stuck client never holds the others up.
- **chaos gate** — a stream opened with ``chaosGate=<tag>`` is skipped
  while the gate ``apiserver.watch.write.<tag>`` is armed: its queue grows
  on exact depth while its siblings stream.

A stream ends as before: the writer writes the terminating chunk after the
last batch (or the bounded-lag 410 frame), shuts the socket down and sets
the stream's ``done``; the parked handler, woken by the shutdown, stops
the watcher and closes the connection. A client that hangs up wakes its
parked handler (``POLLRDHUP``), which marks the stream hung up and ready:
the writer drops it without writing. Websocket watch streams read the
client's frames and keep their own thread (``_stream_watch_websocket``).
"""

from __future__ import annotations

import logging
import os
import select
import socket
import ssl
import threading
import time
from typing import Dict, Optional, Set

from kubernetes_tpu.util import chaos, interpprobe, tracing

__all__ = ["WatchWriter", "MAX_ITEMS"]

MAX_ITEMS = 128          # events a stream gives one pass (next_batch's)
GATE_RECHECK_S = 0.05    # how often an armed chaos gate is looked at again
_END = b"0\r\n\r\n"      # the chunked stream's terminating chunk
_WOULD_BLOCK = (BlockingIOError, ssl.SSLWantWriteError)

_log = logging.getLogger("kubernetes_tpu.apiserver.watchwriter")


class _Stream:
    """One chunked watch connection, from registration to its end."""

    __slots__ = ("watcher", "translate", "version", "sock", "gate",
                 "pending", "ending", "hungup", "done")

    def __init__(self, watcher, translate, version: str, sock, gate: str):
        self.watcher = watcher
        self.translate = translate
        self.version = version
        self.sock = sock
        self.gate = gate            # the chaos point, "" when untagged
        self.pending = None         # bytes the kernel has not taken yet
        self.ending = False         # the last bytes are written or pending
        self.hungup = False         # the client went away: write nothing
        self.done = threading.Event()


class WatchWriter:
    """The fan-out writer of one ``APIServer`` (see the module doc)."""

    def __init__(self, apisrv):
        self._api = apisrv
        self._lock = threading.Lock()
        self._ready: Set[_Stream] = set()
        self._wake = threading.Event()
        self._streams: Set[_Stream] = set()
        self._blocked: Dict[int, _Stream] = {}   # fd -> stream with pending
        self._gated: Set[_Stream] = set()
        # while the writer polls blocked sockets it cannot wait on the
        # event: a stream that becomes ready then wakes it through a pipe
        self._polling = False
        self._piped = False
        self._pipe = None
        self._stopping = False
        self._thread: Optional[threading.Thread] = None

    # -- the handlers' side -------------------------------------------------

    def serve(self, watcher, translate, version: str, sock,
              gate_tag: str = "") -> None:
        """Register one stream whose headers are sent, and park the calling
        handler thread until the stream has ended."""
        stream = _Stream(watcher, translate, version, sock,
                         "apiserver.watch.write." + gate_tag if gate_tag
                         else "")
        with self._lock:
            if self._stopping:
                return
            self._streams.add(stream)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="watch-writer")
                self._thread.start()
        sock.setblocking(False)
        watcher.set_ready_hook(lambda _w: self._mark(stream))
        self._mark(stream)          # what was queued before the hook
        poller = select.poll()
        poller.register(sock, select.POLLRDHUP)
        # a hang-up, or the writer's shutdown at the stream's end
        poller.poll()
        if not stream.done.is_set():
            stream.hungup = True
            self._mark(stream)
        stream.done.wait()

    def _mark(self, stream: _Stream) -> None:
        """The ready hook: ``stream`` has events (or its end) to write."""
        with self._lock:
            self._ready.add(stream)
            self._poke()
        # cleared only under the lock, after the writer saw no stream
        # ready: a set event with this stream ready cannot be missed
        if not self._wake.is_set():
            self._wake.set()

    def _poke(self) -> None:
        """Wake a writer that polls blocked sockets (it cannot see the
        event then): one byte down its pipe, once a poll. Under the lock,
        so the pipe cannot close meanwhile; the write never blocks."""
        if self._polling and not self._piped:
            self._piped = True
            os.write(self._pipe[1], b"\0")

    def stop(self) -> None:
        """Write what the streams have queued (their ends too), as far as
        their sockets take it without waiting, end every stream and let
        the thread go."""
        with self._lock:
            self._stopping = True
            thread = self._thread
            self._poke()
        self._wake.set()
        if thread is not None:
            thread.join(timeout=5.0)

    # -- the writer thread --------------------------------------------------

    def _run(self) -> None:
        tracing.role("watch_send")
        try:
            while True:
                self._wait()
                try:
                    self._pass()
                except Exception:  # noqa: BLE001 — one bad pass ends no one
                    _log.exception("watch writer pass failed")
                if self._stopping:
                    break
        finally:
            with self._lock:
                streams, self._streams = self._streams, set()
                pipe, self._pipe = self._pipe, None
                self._polling = False
            for stream in streams:
                self._finish(stream)
            for fd in pipe or ():
                os.close(fd)
            tracing.role_end()

    def _wait(self) -> None:
        """Sleep until a stream is ready, a blocked socket can take bytes,
        or an armed gate is due a look: one counted wait."""
        with self._lock:
            if self._ready or self._stopping:
                return
            self._wake.clear()
            poll = bool(self._blocked)
            if poll and self._pipe is None:
                self._pipe = os.pipe()
                os.set_blocking(self._pipe[1], False)
            self._polling, self._piped = poll, False
        timeout = GATE_RECHECK_S if self._gated else None
        interpprobe.hop(interpprobe.WAIT)
        if not poll:
            self._wake.wait(timeout)
            return
        poller = self._poller()
        poller.register(self._pipe[0], select.POLLIN)
        poller.poll(None if timeout is None else timeout * 1000.0)
        with self._lock:
            self._polling = False
            piped = self._piped
        if piped:
            os.read(self._pipe[0], 64)

    def _poller(self):
        poller = select.poll()
        for fd in self._blocked:
            poller.register(fd, select.POLLOUT)
        return poller

    def _pass(self) -> None:
        """One wake's work: the linger, then every ready stream once, and
        every blocked stream whose socket takes bytes again."""
        writable = set()
        if self._blocked:
            writable = {self._blocked[fd] for fd, _ in self._poller().poll(0)
                        if fd in self._blocked}
        with self._lock:
            ready = self._ready
            # a backlog fills a batch by itself: no linger for it
            full = writable or any(s.watcher.queued() >= MAX_ITEMS
                                   for s in ready)
        if ready and not full and not self._stopping:
            interpprobe.hop(interpprobe.WAIT)
            time.sleep(self._api.watch_write_linger)
        with self._lock:
            ready, self._ready = self._ready, set()
        ready |= writable
        for stream in [s for s in self._gated if not self._held(s)]:
            self._gated.discard(stream)
            ready.add(stream)
        written = 0
        for stream in ready:
            written += self._serve(stream)
        if written:
            self._api.metric_fanout_wakes.inc()
            self._api.metric_fanout_streams_written.inc(by=written)

    @staticmethod
    def _held(stream: _Stream) -> bool:
        """The stream's chaos gate is armed."""
        return bool(stream.gate) and chaos.gate_armed(stream.gate)

    def _serve(self, stream: _Stream) -> int:
        """Write one batch of ``stream``'s events; 1 when it wrote frames."""
        if stream.done.is_set():
            return 0
        if stream.hungup:
            self._finish(stream)
            return 0
        if stream.pending is not None and not self._flush(stream):
            return 0                 # still blocked: its queue absorbs
        if stream.ending:
            self._finish(stream)
            return 0
        if self._held(stream):
            self._gated.add(stream)
            return 0
        api = self._api
        events, ended = stream.watcher.drain(MAX_ITEMS)
        if not ended and stream.watcher.queued():
            with self._lock:
                self._ready.add(stream)     # the rest, next pass
        wrote = 0
        with tracing.phase("watch.send", traced=False):
            t0 = time.monotonic()
            parts, lagged = api.translate_batch(
                events, stream.translate, stream.version, ws_frames=False)
            if parts:
                api.metric_fanout_frames.observe(len(parts))
                wrote = 1
            if lagged or ended:
                parts.append(_END)
                stream.ending = True
            if parts:
                stream.pending = memoryview(b"".join(parts))
                self._flush(stream)
            if wrote:
                api.metric_fanout_seconds.observe(time.monotonic() - t0)
        if stream.ending and stream.pending is None:
            self._finish(stream)
        return wrote

    def _flush(self, stream: _Stream) -> bool:
        """One non-blocking send of the stream's pending bytes; True once
        the kernel has taken them all."""
        fd = stream.sock.fileno()
        t0 = time.monotonic_ns()
        try:
            n = stream.sock.send(stream.pending)
        except _WOULD_BLOCK:
            n = 0
        except OSError:
            interpprobe.sent(t0)
            stream.hungup = True
            self._finish(stream)
            return False
        interpprobe.sent(t0)
        if n < stream.pending.nbytes:
            stream.pending = stream.pending[n:]
            self._blocked[fd] = stream
            return False
        stream.pending = None
        self._blocked.pop(fd, None)
        return True

    def _finish(self, stream: _Stream) -> None:
        """The stream has ended: let go of its socket, wake its handler."""
        if stream.done.is_set():
            return
        with self._lock:
            self._streams.discard(stream)
        self._gated.discard(stream)
        self._blocked.pop(stream.sock.fileno(), None)
        stream.pending = None
        try:
            stream.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        stream.done.set()
