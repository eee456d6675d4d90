"""ServingFrontend: the network serving plane over the JSON-lines
substrate.

PRs 8-13 built a production-grade serving CORE — continuous batching,
paged decode with KV sharing, preemption-safe snapshots, graceful
degradation — all of it in-process. This module is the missing
outermost layer: a socket front end (the serving split the TensorFlow
system paper describes — model runtime behind an RPC plane) on the one
wire protocol every control-plane service in the repo already speaks
(``distributed.master.serve_json_lines``), so "millions of users"
reach the runtime without this repo growing an RPC dependency.

Endpoints (one JSON line per request; see docs/SERVING.md "Network
front end" for the full wire grammar):

* ``predict`` — unary, routed to a :class:`serving.server.BatchingServer`.
  Deadlines ride the wire; the server's typed admission errors
  (``QueueFullError``/``DeadlineExceededError``/``DegradedError``...)
  serialize as typed wire errors (``serving.client.error_to_wire``)
  the client re-raises as the SAME exception classes.
* ``generate`` — STREAMING, routed to a
  :class:`serving.generation.SlotDecodeSession`: token chunks are
  flushed to the socket as each decode dispatch (``run_multi_step``
  chunk) completes, not at end-of-generation. ``n > 1`` forks a
  best-of-N group through ``admit_group`` (one encoder forward, shared
  KV by reference) and ``prefix_tokens`` rides the prefix cache — the
  whole KV-reuse layer works remotely. Solo requests that find the
  pool full ride the session's PERSISTENT queue (so a preemption
  snapshot banks the backlog); forks are admit-or-reject (their
  worst-case page reservation is too large to head-of-line park).
* ``metrics`` — the process's Prometheus scrape (the registry text);
  ``health`` — the ``HealthMonitor`` states; ``stats`` /
  ``take_result`` — introspection + post-preemption result claims.

Disconnect safety is the load-bearing property: a client that dies (or
cancels) mid-stream must cost the pool NOTHING. Three hooks converge on
the same teardown — the substrate's per-connection close callback, the
in-band ``cancel`` line or EOF read by the connection watcher, and the
stream generator's ``GeneratorExit`` (a failed socket write) — each
routing to ``SlotDecodeSession.cancel`` / ``drop_pending`` on the decode
worker thread, which returns the slot and drops the page references;
``pool_conserved`` (free + unique-allocated == P - 1) holds afterwards,
asserted by the tests' kill-mid-stream legs (``tests/test_frontend.py``).

Who reads a cancel: ONE watcher thread (``_ConnWatcher``) holds a
selector over the sockets of the connections that have a stream in
flight. A handler thread blocks on its stream's queue and wakes only for
a message of the decode worker's or the watcher's verdict; it polls
nothing. On a ``cancel`` line or EOF the watcher cancels the stream on
the decode worker at once and posts the verdict; the handler writes the
terminal ``cancelled`` event (or just returns on EOF). Every cancel line
is answered EXACTLY once, and a client may send its next request as soon
as it has read a terminal event, so: a handler takes its connection out
of the watcher (``unwatch``: under the lock every read of the watcher's
is made under) BEFORE it writes ``end``, ``cancelled`` or an error line,
and if the watcher had already consumed a cancel line that no
``cancelled`` has answered, the handler writes that one ack (``{"event":
"cancelled", "idle": true}``, what the substrate answers a cancel with
no stream in flight) right after its terminal line. A cancel line still
in the socket at the hand-back is the substrate's to answer, as ever.

Preemption composes with PR 13: construct the
``DecodeSnapshotManager(install_signal_handlers=True)`` FIRST, then the
frontend with ``install_signal_handlers=True`` — on SIGTERM the
frontend stops the transport and chains to the manager, which finishes
the in-flight dispatch, banks a final snapshot (live slots AND the
queued backlog) and re-raises, so the process dies BY the signal with
the work recoverable (``restore()`` + ``pump()`` or a fresh frontend).

One dedicated decode-worker thread owns the session (admissions,
steps, cancellations all serialize through it — the session is not
thread-safe and must not become so: the zero-compile contract lives in
its single-threaded dispatch discipline); handler threads only move
messages between that worker and their sockets, and the watcher thread
only reads cancels (``_DecodeWorker.cancel`` is any thread's to call).
"""

import base64
import json
import os
import queue
import select
import selectors
import shutil
import signal
import socket
import threading
import time
from collections import deque

import numpy as np

from paddle_tpu.distributed.master import (
    close_json_server,
    serve_json_lines,
)
from paddle_tpu.observability import lock_witness
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.metrics_registry import (
    REGISTRY as _REGISTRY,
    SERVING_BUCKETS,
)
from paddle_tpu.serving.client import (
    MigrationBusyError,
    decode_array,
    encode_array,
    error_from_wire,
    error_to_wire,
)
from paddle_tpu.serving.degradation import SHED as _SHED
from paddle_tpu.serving.degradation import DegradedError
from paddle_tpu.serving.generation import (
    NoFreeGroupError,
    NoFreePageError,
    NoFreeSlotError,
)
from paddle_tpu.serving.server import (
    DeadlineExceededError,
    QueueFullError,
    ServerClosedError,
    ServingError,
)

__all__ = ["ServingFrontend"]


_fe_request_seconds = _REGISTRY.histogram(
    "paddle_tpu_frontend_request_seconds",
    "wire request latency by endpoint and outcome (streams: request "
    "arrival to terminal event)",
    labels=("endpoint", "outcome"), buckets=SERVING_BUCKETS)
_fe_active_conns = _REGISTRY.gauge(
    "paddle_tpu_frontend_active_connections",
    "established frontend client connections")
_fe_bytes_sent = _REGISTRY.counter(
    "paddle_tpu_frontend_bytes_sent_total",
    "response bytes written to frontend sockets")
_fe_bytes_received = _REGISTRY.counter(
    "paddle_tpu_frontend_bytes_received_total",
    "request bytes read from frontend sockets")
_fe_ttft = _REGISTRY.histogram(
    "paddle_tpu_frontend_ttft_seconds",
    "stream time-to-first-token: generate request arrival to the first "
    "token chunk flushed", buckets=SERVING_BUCKETS)
_fe_streams_total = _REGISTRY.counter(
    "paddle_tpu_frontend_streams_total",
    "generate streams by terminal outcome",
    labels=("outcome",))  # ok | cancelled | disconnect | error | ...


def _outcome(exc):
    """Metrics outcome label for one typed failure."""
    if isinstance(exc, QueueFullError):
        return "queue_full"
    if isinstance(exc, DeadlineExceededError):
        return "deadline"
    if isinstance(exc, DegradedError):
        return "degraded"
    if isinstance(exc, ServerClosedError):
        return "closed"
    if isinstance(exc, (NoFreeSlotError, NoFreePageError,
                        NoFreeGroupError)):
        return "no_capacity"
    return "error"


# what the connection watcher puts on a stream's queue beside the decode
# worker's messages (dicts): its verdict on the stream's connection
_CANCEL = "cancel"   # the client's in-band cancel line was read
_EOF = "eof"         # the client is gone
_LOOK = "look"       # TLS: bytes arrived, the handler reads them itself


class _Stream(object):
    """One wire generate stream: the handler thread consumes ``q``;
    the decode worker produces into it and tracks the live slots; the
    connection watcher puts its verdict there."""

    __slots__ = ("q", "spec", "cancelled", "live", "rid", "done",
                 "beam_lane", "beam_rid", "wakeups", "empty_wakeups")

    def __init__(self, spec):
        # every event a put and a wake-up of the handler, some thousand
        # a second under one interpreter lock: the queue whose put and
        # get are one C call each, not a mutex and three conditions
        self.q = queue.SimpleQueue()
        # the handler's returns from q.get(), and those of them that
        # brought nothing to write or act on (written by the handler
        # alone, folded into the watcher's counts as the stream ends)
        self.wakeups = 0
        self.empty_wakeups = 0
        self.spec = spec       # {"src", "src_len", "n", "prefix", "beam"}
        self.cancelled = threading.Event()
        self.live = {}         # slot -> member index
        self.rid = None        # session request id when deferred
        self.done = False
        self.beam_lane = None  # beam streams: the lane this stream owns
        self.beam_rid = None   # ... and its banked-result claim id


class _HandlerAccount(object):
    """What the handler threads cost the interpreter, kept with tracing
    on: each handler's CPU seconds so far (request parsing and socket
    writes included) and the chunks it wrote, under its connection's id
    (one handler thread a connection) while the connection is open, and
    summed for the closed. A handler writes its own key at the end of a
    request (one clock read a request, never a chunk; a single-key store
    needs no lock); the lock keeps a close's fold and the worker's sum
    apart, so the totals only grow."""

    def __init__(self):
        self._mu = lock_witness.make_lock("serving.frontend.handlers")
        self.open = {}          # connection id -> (cpu seconds, chunks)
        self._closed = (0.0, 0)

    def request_done(self, conn_id, chunks):
        """On the handler's own thread."""
        self.open[conn_id] = (time.thread_time(),
                              self.open.get(conn_id, (0.0, 0))[1] + chunks)

    def closed(self, conn_id):
        """On the handler's own thread, as its connection closes."""
        with self._mu:
            acct = self.open.pop(conn_id, None)
            if acct is not None:
                self._closed = (self._closed[0] + time.thread_time(),
                                self._closed[1] + acct[1])

    def totals(self):
        """``(cpu seconds, chunks)`` of every handler so far."""
        with self._mu:
            cpu, chunks = self._closed
            for acct in list(self.open.values()):
                cpu += acct[0]
                chunks += acct[1]
        return cpu, chunks


class _Watch(object):
    """One connection with a stream in flight, as the watcher holds it."""

    __slots__ = ("conn", "stream", "armed", "owed")

    def __init__(self, conn, stream):
        self.conn = conn
        self.stream = stream
        self.armed = False     # registered in the selector
        self.owed = False      # a cancel line was consumed, not answered


class _ConnWatcher(object):
    """The one thread that reads every streaming connection's in-band
    cancel or EOF, so that a handler thread sleeps on its stream's queue
    until there is something to write.

    A handler puts its connection in (``watch``) when its stream starts
    and takes it out (``unwatch``) BEFORE it writes any terminal event.
    In between, the watcher blocks in a selector over the watched
    sockets (no interpreter lock held); when one turns readable it
    peeks, reads a whole line if there is one, and on a ``cancel`` line
    or EOF cancels the stream on the decode worker itself and posts the
    verdict (``_CANCEL`` / ``_EOF``) to the stream's queue.

    The hand-back rule that keeps the wire in step: every read of a
    connection happens under ``_mu`` and only while its entry is still
    the watched one, and ``unwatch`` takes ``_mu`` too — once it
    returns, the watcher is not reading that connection and never will
    again, so the next request's line is the substrate's. ``unwatch``
    returns whether the watcher consumed a cancel line (the protocol
    answers each exactly once): the handler that writes another
    terminal event first (the stream ended while the cancel was in
    flight) owes the client the one ``cancelled`` ack after it.

    A readable socket that holds no whole line yet (a fragmented
    cancel, a trickling client) is taken out of the selector and looked
    at again after ``pause_s``: ``readline`` would block the watcher and
    a level-triggered selector would spin it. A TLS connection is never
    read here (an SSL object is not safe to read on one thread while
    another writes): raw readability posts ``_LOOK`` and the handler
    reads the line on its own thread, then puts the connection back
    (``rearm``) if it was not a cancel.
    """

    def __init__(self, cancel, pause_s, tls=False):
        self._cancel = cancel    # _DecodeWorker.cancel: any thread's
        self._pause = float(pause_s)
        self._tls = bool(tls)
        self._mu = lock_witness.make_lock("serving.frontend.watcher")
        self._sel = selectors.DefaultSelector()
        # epoll and kqueue take a registration while select() blocks;
        # the portable fallbacks have to be woken to see it
        self._live_sel = type(self._sel).__name__ in (
            "EpollSelector", "KqueueSelector", "DevpollSelector")
        self._watched = {}       # connection id -> _Watch
        self._paused = {}        # connection id -> when to look again (a
        #                          partial line: watched, out of the selector)
        self._stop = False
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, None)
        # counts (written under _mu, read without; all but the gauge
        # only grow)
        self._wakeups = 0
        self._verdicts = {_CANCEL: 0, _EOF: 0, _LOOK: 0}  # posted
        self._cpu = 0.0
        self._handler_wakeups = 0        # of the streams taken out
        self._handler_empty_wakeups = 0
        self._thread = threading.Thread(
            target=self._loop, name="paddle-tpu-frontend-watcher",
            daemon=True)
        self._thread.start()

    # -- handler-thread API --------------------------------------------------

    def watch(self, conn, stream):
        with self._mu:
            if self._stop:
                return
            w = self._watched[conn.id] = _Watch(conn, stream)
            verdict = self._arm(w)
        self._deliver(w, verdict)

    def unwatch(self, conn):
        """Take ``conn`` out (idempotent). True when the watcher
        consumed a cancel line of this stream's: whoever writes the
        stream's terminal event answers it."""
        with self._mu:
            w = self._watched.pop(conn.id, None)
            if w is None:
                return False
            self._disarm(w)
            self._handler_wakeups += w.stream.wakeups
            self._handler_empty_wakeups += w.stream.empty_wakeups
            return w.owed

    def rearm(self, conn):
        """TLS: the handler read what ``_LOOK`` announced and it was no
        cancel; watch the connection's raw socket again."""
        with self._mu:
            w = self._watched.get(conn.id)
            if w is None or w.armed or self._stop:
                return
            verdict = self._arm(w)
        self._deliver(w, verdict)

    def counts(self):
        """The counts beside ``handler_cpu`` / ``handler_chunks`` (docs/
        OBSERVABILITY.md): all but ``watching`` only grow; the handlers'
        wake-ups are those of the streams that have ended. (A TLS
        frontend's ``_LOOK`` posts are wake-ups here and nothing else:
        the handler that reads the line cancels for itself.) Takes no
        lock: the decode worker reads them once a traced round, just
        when a round's cancels keep ``_mu`` busy, and a wait for a lock
        is a wake-up the worker pays for (6 ms a round when this took
        ``_mu``: my chip runs, PR 37)."""
        return {
            "watching": len(self._watched),
            "wakeups": self._wakeups,
            "cancel": self._verdicts[_CANCEL],
            "eof": self._verdicts[_EOF],
            "cpu": self._cpu,
            "handler_wakeups": self._handler_wakeups,
            "handler_empty_wakeups": self._handler_empty_wakeups,
        }

    def close(self, timeout=10.0):
        """Stop the thread; a handler still parked on a watched stream
        is woken with ``_EOF`` (its connection is about to be severed)."""
        with self._mu:
            if self._stop:
                return
            self._stop = True
            for w in self._watched.values():
                self._disarm(w)
                w.stream.q.put(_EOF)
        self._poke()
        self._thread.join(timeout=timeout)
        with self._mu:
            self._sel.close()
            self._wake_r.close()
            self._wake_w.close()

    # -- selector bookkeeping (under _mu) ------------------------------------

    def _poke(self):
        try:
            self._wake_w.send(b"\0")
        except OSError:
            pass

    def _arm(self, w):
        """Into the selector; the verdict to deliver if that failed."""
        self._paused.pop(w.conn.id, None)
        try:
            self._sel.register(w.conn.sock, selectors.EVENT_READ, w)
        except (ValueError, OSError):
            # a socket that is already closed (the frontend is closing):
            # the verdict wakes its handler
            return self._settle(w, _EOF)
        w.armed = True
        if not self._live_sel:
            self._poke()
        return None

    def _disarm(self, w):
        self._paused.pop(w.conn.id, None)
        if w.armed:
            w.armed = False
            try:
                self._sel.unregister(w.conn.sock)
            except (KeyError, ValueError, OSError):
                pass

    def _settle(self, w, verdict):
        """Under ``_mu``: the connection is read no further, and an
        ``unwatch`` from here on says what the client is owed. Returns
        the verdict for ``_deliver``."""
        self._disarm(w)
        self._verdicts[verdict] += 1
        w.owed = verdict == _CANCEL
        return verdict

    def _deliver(self, w, verdict):
        """Outside ``_mu`` (a handler's ``unwatch`` does not wait for the
        decode worker's condition): ``_CANCEL`` / ``_EOF`` end the
        stream, so it is cancelled on the decode worker from here; then
        the verdict onto the stream's queue, one wake-up of its handler.
        If the handler took the connection out in between (its stream
        ended first) it has answered what was owed and reads no more."""
        if verdict is None:
            return
        if verdict != _LOOK:
            self._cancel(w.stream)
        w.stream.q.put(verdict)

    # -- the thread ----------------------------------------------------------

    def _loop(self):
        while True:
            with self._mu:
                if self._stop:
                    return
                timeout = None
                if self._paused:
                    timeout = max(0.0, min(self._paused.values())
                                  - time.monotonic())
            try:
                ready = self._sel.select(timeout)
            except (OSError, ValueError):
                # a watched socket closed under a select()-based selector:
                # its handler's failed write takes it out; do not spin
                time.sleep(self._pause)
                ready = []
            due = [key.data for key, _ in ready]
            now = time.monotonic()
            with self._mu:
                self._wakeups += 1
                due.extend(self._watched[cid]
                           for cid, at in self._paused.items() if at <= now)
            self._serve(due)
            if _tracing.ENABLED:
                self._cpu = time.thread_time()

    def _serve(self, due):
        """Look at each of ``due`` and deliver its verdict. Runs on the
        watcher's thread and, from ``poll``, on the decode worker's: a
        connection both were told of is settled by whoever takes ``_mu``
        first, and the other finds it disarmed."""
        for w in due:
            if w is None:
                try:
                    self._wake_r.recv(4096)
                except OSError:
                    pass
                continue
            # one connection a hold of _mu: an unwatch waits for one
            # peek and one line, not for the batch
            with self._mu:
                verdict = None
                if (self._watched.get(w.conn.id) is w
                        and (w.armed or w.conn.id in self._paused)
                        and not self._stop):
                    verdict = self._look(w)
            self._deliver(w, verdict)

    def poll(self):
        """On the CALLER's thread (the decode worker's, at the top of a
        pass): the verdicts of the connections readable right now. The
        watcher's own thread needs the interpreter several times a
        cancel line; behind a round's handlers, all woken at once, it
        fell a second behind its clients (PERF.md section 6, PR 43), and
        a slot decodes for nobody until its cancel is read."""
        try:
            ready = self._sel.select(0)
        except (OSError, ValueError):
            return
        # (the wake-up pipe is the thread's own to empty)
        self._serve([key.data for key, _ in ready if key.data is not None])

    def _look(self, w):
        """Under ``_mu``, a connection that turned readable (or whose
        pause is over): the verdict to deliver, if any."""
        if self._tls:
            return self._settle(w, _LOOK)
        verdict = _read_verdict(w.conn, block=False)
        if verdict == _PARTIAL:
            self._disarm(w)
            self._paused[w.conn.id] = time.monotonic() + self._pause
            return None
        if verdict is None:
            # a whole line that was no cancel, consumed and ignored
            return None if w.armed else self._arm(w)
        return self._settle(w, verdict)


_MSG_DONTWAIT = getattr(socket, "MSG_DONTWAIT", 0)
_PARTIAL = "partial"   # _read_verdict: bytes, but no whole line yet


def _peer_closed(sock):
    """The peer has shut its side down (Linux's POLLRDHUP; elsewhere
    False): what a peek that still finds a partial line cannot say."""
    if not hasattr(select, "POLLRDHUP"):
        return False
    poller = select.poll()
    poller.register(sock, select.POLLRDHUP)
    return bool(poller.poll(0))


def _read_verdict(conn, block):
    """What a readable streaming connection holds: ``_CANCEL`` for the
    client's in-band cancel line, ``_EOF`` when it disconnected,
    ``_PARTIAL`` when the bytes there hold no whole line yet
    (``readline`` would block), None for anything else. Safe
    mid-stream: the protocol sends nothing else while a stream is in
    flight, so a pipelined request line is protocol misuse, consumed and
    ignored. ``block`` (TLS, on the handler's own thread: an SSL socket
    cannot be peeked) reads the line without looking first."""
    if not block:
        try:
            peek = conn.sock.recv(4096, socket.MSG_PEEK | _MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            return None
        except (OSError, ValueError):
            return _EOF
        if not peek:
            return _EOF
        if b"\n" not in peek:
            # (a partial line and then EOF is EOF: no newline will come)
            return _EOF if _peer_closed(conn.sock) else _PARTIAL
    try:
        line = conn.rfile.readline()
    except (OSError, ValueError):
        return _EOF
    if not line:
        return _EOF
    try:
        msg = json.loads(line)
    except ValueError:
        return _EOF
    if isinstance(msg, dict) and msg.get("method") == "cancel":
        return _CANCEL
    return None


class _DecodeWorker(object):
    """The one thread that owns the SlotDecodeSession.

    Handler threads enqueue admissions/cancellations; the worker admits
    (directly for fork groups, through the session's persistent queue
    for solo requests — that queue is what a preemption snapshot
    banks), steps the shared pool, and streams each tracked slot's
    per-dispatch token increments to its wire stream. Finished slots
    that no stream owns (a restored process's orphaned backlog) are
    banked in the session's result bank, exactly like ``pump()``.

    Every event the worker sends goes through ONE first-in-first-out
    outbox (``_emit``), so a stream's order (``admitted``, ``tokens``
    with contiguous ``seq``, ``end`` or an error line) cannot invert.
    With no request waiting for a slot each phase of a pass puts its
    events as it ends. While requests wait (``_hold``), a dispatch's
    events are kept until the NEXT decode dispatch is launched and put
    while the chip runs it (``_in_flight``, which the session hands to
    its dispatch): the wake-ups of some hundred handler threads then
    stand beside the device's time, not in line with it, at the price of
    one pass's cancels and admissions of delay for those tokens, which
    below the knee would buy nothing. The clients answer those events
    (cancels among them) while the worker's thread is busiest, so while
    it holds events it reads the connections' verdicts itself at the top
    of a pass (``_ConnWatcher.poll``) and does not wait for the
    watcher's thread to be given the interpreter.
    """

    def __init__(self, session, max_backlog=64, handlers=None):
        self._s = session
        # the frontend's account of its handler threads, for the rounds
        self._handlers = _HandlerAccount() if handlers is None else handlers
        self.watcher = None      # the frontend's _ConnWatcher, likewise
        self._cond = lock_witness.make_condition("serving.frontend.decode")
        self._incoming = deque()
        self._cancels = deque()
        self._ops = deque()      # (fn, box, done) session ops (snapshot/
        #                          restore) executed at a quiesce point
        self._stop = False
        self._drain = True
        self._slot_stream = {}   # slot -> (stream, member)
        self._rid_stream = {}    # rid -> stream (queued, not yet admitted)
        self._prev_pos = {}      # slot -> last streamed position
        self._beam_stream = {}   # lane -> stream (beam generations)
        self._outbox = deque()   # (stream, event) not yet in stream.q
        self._hold = False       # the outbox waits for the next launch
        self._max_backlog = int(max_backlog)
        self._thread = threading.Thread(
            target=self._loop, name="paddle-tpu-frontend-decode",
            daemon=True)
        self._thread.start()

    # -- handler-thread API --------------------------------------------------

    def submit(self, stream):
        with self._cond:
            if self._stop:
                stream.q.put(error_to_wire(
                    ServerClosedError("frontend is closed")))
                return
            self._incoming.append(stream)
            self._cond.notify_all()

    def cancel(self, stream):
        stream.cancelled.set()
        with self._cond:
            self._cancels.append(stream)
            self._cond.notify_all()

    def stop(self, drain=True, timeout=60.0):
        with self._cond:
            self._stop = True
            self._drain = bool(drain)
            self._cond.notify_all()
        self._thread.join(timeout=timeout)

    def call(self, fn, timeout=60.0):
        """Run ``fn()`` ON the decode worker thread, between dispatches
        (a quiesce point — the session is never mid-dispatch there).
        This is how the snapshot/restore wire endpoints reach the
        session without violating the one-owner-thread discipline."""
        box = {}
        done = threading.Event()
        with self._cond:
            if self._stop:
                raise ServerClosedError("frontend is closed")
            self._ops.append((fn, box, done))
            self._cond.notify_all()
        if not done.wait(timeout=timeout):
            raise TimeoutError("decode worker op timed out")
        if "exc" in box:
            raise box["exc"]
        return box["val"]

    def _run_ops(self, ops):
        for fn, box, done in ops:
            try:
                box["val"] = fn()
            except BaseException as exc:  # noqa: BLE001 - re-raised in call
                box["exc"] = exc
            done.set()

    def _fail_ops(self):
        with self._cond:
            ops = list(self._ops)
            self._ops.clear()
        for _fn, box, done in ops:
            box["exc"] = ServerClosedError("frontend is closed")
            done.set()

    # -- the outbox (worker thread only) -------------------------------------

    def _emit(self, stream, event):
        self._outbox.append((stream, event))

    def _flush(self, deferred=False):
        """Put the outbox's events, oldest first. The caller stands in a
        round span (``handoff`` wherever it is not a phase's own)."""
        out = self._outbox
        n = len(out)
        while out:
            stream, event = out.popleft()
            stream.q.put(event)
        if n and _tracing.ENABLED:
            _tracing.round_count("handoff_events", n)
            if deferred:
                _tracing.round_count("handoff_deferred", n)

    def _settle(self):
        """A phase's end: its events go out now, unless they wait for
        the next launch."""
        if not self._hold:
            self._flush()

    def _release(self):
        """Whatever is held goes out now: no launch will carry it (the
        pass made no dispatch, is about to block, or something has to
        see the streams as the session has them: an op at its quiesce
        point, a failure's or a close's error lines)."""
        self._hold = False
        if self._outbox:
            with _tracing.span("handoff"):
                self._flush()

    def _in_flight(self):
        """The session's ``in_flight``: runs inside the decode dispatch,
        launched and not yet waited for. Must not raise into the
        executor; what a failure leaves is put when ``step`` returns."""
        if not self._outbox:
            return
        try:
            with _tracing.span("handoff"):
                self._flush(deferred=True)
        except Exception:  # noqa: BLE001 - logged, the dispatch goes on
            import logging

            logging.getLogger("paddle_tpu.serving").exception(
                "event flush inside the decode dispatch failed")

    # -- worker loop ---------------------------------------------------------

    def _loop(self):
        while True:
            # tracing on: each pass that does work is one round record
            # (observability/tracing.py), its phases the child spans
            rd = _tracing.round_begin() if _tracing.ENABLED else None
            progressed = False
            try:
                alive, progressed = self._pass()
            finally:
                if rd is not None:
                    if progressed:
                        self._count_handlers()
                    _tracing.round_end(rd, keep=progressed)
            if not alive:
                return
            if not progressed:
                # a whole pass moved nothing — the backlog is
                # capacity/degradation-deferred with no live slots to
                # drain it (e.g. leaked pages shrank capacity): sleep
                # instead of spinning on admit_pending, but wake
                # immediately for new work. Deliberately NOT gated on
                # _stop: a close(drain=True) over an undrainable
                # backlog must idle at this cadence, not burn a core
                # until the join timeout
                with self._cond:
                    if not self._incoming and not self._cancels:
                        self._cond.wait(0.1)

    def _count_handlers(self):
        """The handler threads' account so far onto the round's root
        (keys new to the round, so the add is a store). A reader takes
        last less first over its rounds."""
        cpu, chunks = self._handlers.totals()
        _tracing.round_count("handler_cpu", cpu)
        _tracing.round_count("handler_chunks", chunks)
        if self.watcher is not None:
            counts = self.watcher.counts()
            for key in ("handler_wakeups", "handler_empty_wakeups"):
                _tracing.round_count(key, counts.pop(key))
            for key, n in counts.items():
                _tracing.round_count("watcher_" + key, n)

    def _pass(self):
        """One pass of the worker loop. Returns ``(alive, progressed)``:
        whether the worker lives on, and whether the pass moved
        anything."""
        s = self._s
        if self._hold and self.watcher is not None:
            # what the clients answered to the flush made in flight
            with _tracing.span("verdicts"):
                self.watcher.poll()
        with _tracing.span("wait"), self._cond:
            while (not self._incoming and not self._cancels
                    and not self._ops
                    and not self._stop and not s.active_slots
                    and not (s.pending_requests and s.free_slots)):
                # the timeout re-checks capacity-deferred backlog
                # (a NoFreePage defer relaxes only as leaks/cache
                # pressure do, not on any notify)
                self._release()
                self._cond.wait(0.25)
            incoming = list(self._incoming)
            self._incoming.clear()
            cancels = list(self._cancels)
            self._cancels.clear()
            ops = list(self._ops)
            self._ops.clear()
            stop, drain = self._stop, self._drain
        progressed = bool(incoming or cancels or ops)
        # the pass's cancelled streams are torn down together: their
        # live slots are ONE cancel_many, one table dispatch
        self._teardown(cancels)
        # ops run at this quiesce point: after cancels (so a drain's
        # "no live streams" check sees the teardowns) and before
        # this pass's admissions/dispatch; the streams have what the
        # session has by then
        if ops:
            self._release()
            self._run_ops(ops)
        if incoming:
            with _tracing.span("enqueue"):
                for stream in incoming:
                    if stop:
                        self._emit(stream, error_to_wire(
                            ServerClosedError("frontend is closed")))
                        stream.done = True
                    elif not stream.cancelled.is_set():
                        self._admit(stream)
                self._settle()
        if stop and not drain:
            self._abort_all()
            self._fail_ops()
            return False, progressed
        progressed |= self._admit_backlog()
        stepped = False
        if s.active_slots:
            try:
                self._step_once()
                stepped = True
            except Exception as exc:  # noqa: BLE001 - typed below
                # a hard decode failure (not the classified-retry
                # transients — those were retried inside the
                # executor) must not kill the worker and wedge
                # every stream: every tracked stream gets the
                # typed failure, its slots are cancelled, the
                # worker lives on for the next admission
                self._fail_tracked(exc)
            progressed = True
        if not stepped:
            self._release()
        if (stop and drain and not s.active_slots
                and not s.pending_requests and not self._slot_stream
                and not self._rid_stream and not self._beam_stream):
            self._fail_ops()
            return False, progressed
        return True, progressed

    def _admit_backlog(self):
        """Admit queued requests and map the newly admitted ones back
        to their wire streams. ``admit_pending`` raising mid-way (a
        failed admission dispatch past the retry budget, a request the
        session type refuses — e.g. a forced prefix on a dense
        session) must not kill the worker: the failed request's stream
        gets the typed error, requests admitted BEFORE the failure are
        recovered from the session's owner map. Returns True when the
        pass made progress (an admission or an error delivery) — a
        fully deferred backlog returns False so the loop can throttle
        instead of spinning."""
        s = self._s
        before = set(s.pending_requests)
        exc = None
        try:
            s.admit_pending()
        except Exception as e:  # noqa: BLE001 - delivered to the stream
            exc = e
        progressed = before != set(s.pending_requests)
        self._hand_off_admitted()
        if exc is not None:
            # the request that failed was popped but neither admitted
            # nor re-deferred: its id is gone from both views
            lost = (before - set(s.pending_requests)
                    - set(s._owner.values()))
            for rid in lost:
                stream = self._rid_stream.pop(rid, None)
                if stream is not None and not stream.done:
                    stream.done = True
                    self._emit(stream, error_to_wire(exc))
            self._settle()
            progressed = True
        return progressed

    def _hand_off_admitted(self):
        """Newly admitted = owner entries a wire stream is waiting on
        (orphaned rids — a restored process's backlog — stay owned and
        bank through the pump discipline on finish)."""
        s = self._s
        late = []  # admitted after their client had cancelled
        with _tracing.span("handoff"):
            for slot, rid in list(s._owner.items()):
                stream = self._rid_stream.pop(rid, None)
                if stream is None:
                    continue
                if stream.cancelled.is_set():
                    late.append(slot)
                    continue
                self._track(stream, {slot: 0})
                self._emit(stream, self._admitted_event(stream))
            self._settle()
            self._safe_cancel(late)

    def _fail_tracked(self, exc):
        wire = error_to_wire(exc)
        streams = self._tracked_streams()
        # teardown marks the streams done; the terminal error line must
        # still be delivered (a tracked stream has not yet seen a
        # terminal event — it was live until this failure)
        self._teardown(streams)
        for stream in streams:
            self._emit(stream, dict(wire))
        self._release()

    def _tracked_streams(self):
        """The streams that own a live slot or a beam lane, each once,
        in the order they were tracked."""
        return list(dict.fromkeys(
            [st for st, _m in self._slot_stream.values()]
            + list(self._beam_stream.values())))

    def _admit(self, stream):
        s = self._s
        spec = stream.spec
        tid = spec.get("trace_id")
        t_admit = time.time() if tid else 0.0
        try:
            if spec.get("attach") is not None:
                self._attach_stream(stream)
            elif spec.get("beam"):
                # beam request: admit-or-reject into one lane (the
                # beam's K x worst-case reservation never queues);
                # per-dispatch survivor chunks stream from _step_once,
                # the final n-best from the session's result bank
                with _tracing.span("admit"):
                    lane = s.admit_beam(spec["src"], spec["src_len"],
                                        prefix_tokens=spec["prefix"])
                stream.beam_lane = lane
                stream.beam_rid = s.register_beam_owner(lane)
                self._beam_stream[lane] = stream
                for k, slot in enumerate(s.beam_slots(lane)):
                    stream.live[slot] = k
                self._trace_admitted(stream, t_admit, kind="beam")
                self._emit(stream, self._admitted_event(stream))
            elif spec["n"] == 1:
                # the shed answer at the WIRE edge: a shed session
                # refuses with the typed retriable DegradedError
                # (retry-after hint) instead of silently parking the
                # request behind a queue it is trying to drain. A pure
                # STATE read — never observe(): the admission path's
                # own gate observes, and a second observation per
                # request would let one request step the monitor two
                # recovery levels (forks don't need this check at all:
                # admit_group gates internally)
                monitor = s._monitor
                if monitor is not None and s.health == _SHED:
                    raise monitor.reject("admission (draining "
                                         "in-flight)")
                # solo requests ride the session's persistent queue:
                # banked by a decode snapshot, admitted in arrival
                # order by admit_pending (possibly this same pass)
                if len(s.pending_requests) >= self._max_backlog:
                    raise QueueFullError(
                        "decode backlog at max_stream_backlog %d"
                        % self._max_backlog)
                rid = s.enqueue(spec["src"], spec["src_len"],
                                prefix_tokens=spec["prefix"],
                                trace_id=tid)
                stream.rid = rid
                self._rid_stream[rid] = stream
                ev = {"ok": True, "event": "queued", "id": int(rid)}
                if tid:
                    ev["trace_id"] = tid
                self._emit(stream, ev)
            else:
                # forks are admit-or-reject: their n x worst-case page
                # reservation is too large to head-of-line park in the
                # backlog (docs/SERVING.md "Network front end")
                with _tracing.span("admit"):
                    slots = s.admit_group(
                        spec["src"], n=spec["n"],
                        src_len=spec["src_len"],
                        prefix_tokens=spec["prefix"])
                self._track(stream,
                            {slot: m for m, slot in enumerate(slots)})
                self._trace_admitted(stream, t_admit, kind="group")
                self._emit(stream, self._admitted_event(stream))
        except Exception as exc:  # noqa: BLE001 - typed to the wire
            stream.done = True
            self._emit(stream, error_to_wire(exc))

    def _attach_stream(self, stream):
        """Re-bind a wire stream to an EXISTING solo request by rid —
        the router's failover/drain splice point. The first event is
        ``resumed`` replaying the request's tokens from absolute
        position 1 (trg index 0 is bos); the consumer trims against its
        own ``next_seq``, which handles both a snapshot BEHIND the
        delivered stream (overlap) and a drain snapshot AHEAD of the
        relay (gap-fill) with one splice. Every ``resumed`` variant
        carries ``bos`` — the router synthesizes a correct admission
        from it when a stream failed over before its admission event
        reached the client. Three states attach cleanly:
        banked (finished headless — replay + end), live (track the slot
        mid-flight), pending (wait for admission like a fresh enqueue).
        """
        s = self._s
        rid = int(stream.spec["attach"])
        if rid in s._results:
            trg = s.take_result(rid)
            toks = self._final_tokens(trg, 0)
            stream.done = True
            self._emit(stream, {
                "ok": True, "event": "resumed", "id": rid, "seq": 1,
                "bos": int(s._bos),
                "tokens": [int(t) for t in toks], "finished": True,
                "max_length": int(s._T), "eos": int(s._eos)})
            self._emit(stream, {"ok": True, "event": "end", "id": rid})
            return
        slot = next((sl for sl, r in s._owner.items() if r == rid),
                    None)
        if slot is not None:
            if slot in self._slot_stream:
                raise ServingError(
                    "request %d already has a live stream" % rid)
            stream.rid = rid
            self._track(stream, {slot: 0})
            pos = s._live[slot]["pos"]
            self._emit(stream, {
                "ok": True, "event": "resumed", "id": rid, "seq": 1,
                "bos": int(s._bos),
                "tokens": [int(t)
                           for t in s._live[slot]["trg"][1:pos + 1]],
                "finished": False,
                "max_length": int(s._T), "eos": int(s._eos)})
            return
        if rid in s.pending_requests:
            pend = next((p for p in s._pending if p["id"] == rid), None)
            if pend is not None:
                stream.spec["prefix"] = pend.get("prefix")
            stream.rid = rid
            self._rid_stream[rid] = stream
            self._emit(stream, {
                "ok": True, "event": "resumed", "id": rid, "seq": 1,
                "bos": int(s._bos),
                "tokens": [], "finished": False,
                "max_length": int(s._T), "eos": int(s._eos)})
            return
        raise ServingError("unknown request id %d (not banked, live or "
                           "pending on this frontend)" % rid)

    def _trace_admitted(self, stream, t_admit, kind):
        """Direct admissions (fork groups, beam lanes) bypass the
        session queue, so their admit span and slot->trace binding are
        emitted here; queued solos get both from ``admit_pending``."""
        tid = stream.spec.get("trace_id")
        if not tid:
            return
        tr = _tracing.inflight_get(tid)
        if tr is None:
            return
        tr.span("admit", t_admit, time.time(), kind=kind,
                members=len(stream.live), round=_tracing.round_id())
        for slot in stream.live:
            self._s._slot_traces[slot] = tr

    def _track(self, stream, slots_members):
        s = self._s
        for slot, member in slots_members.items():
            stream.live[slot] = member
            self._slot_stream[slot] = (stream, member)
            # the worker owns the session thread; reading the live
            # mirror directly is the package-internal contract
            self._prev_pos[slot] = s._live[slot]["pos"]

    def _admitted_event(self, stream):
        s = self._s
        prefix = [s._bos] + [int(t)
                             for t in (stream.spec["prefix"] or ())]
        slots = sorted(stream.live, key=lambda sl: stream.live[sl])
        ev = {"ok": True, "event": "admitted",
              "members": len(slots), "slots": [int(x) for x in slots],
              "prefix": prefix, "pos": len(prefix) - 1,
              "max_length": int(s._T), "eos": int(s._eos)}
        tid = stream.spec.get("trace_id")
        if tid:
            ev["trace_id"] = tid
        if stream.rid is not None:
            # solo streams carry their rid for the router's splice/
            # re-attach protocol (fork groups have no single rid and
            # are not resumable)
            ev["id"] = int(stream.rid)
        if stream.beam_lane is not None:
            ev["beam"] = int(stream.beam_lane)
            ev["beam_width"] = int(s.beam_width)
            ev["id"] = int(stream.beam_rid)
        return ev

    def _final_tokens(self, trg, prev):
        """Tokens a finished slot generated past ``prev``: through the
        first eos (the terminal token — post-finish positions are
        forced-eos padding) or the max-length cap."""
        s = self._s
        for idx in range(prev + 1, s._T):
            if int(trg[idx]) == s._eos:
                return trg[prev + 1:idx + 1]
        return trg[prev + 1:s._T]

    def _step_once(self):
        s = self._s
        if _tracing.ENABLED:
            # what the round's length depends on, beside its spans
            _tracing.round_count("live", len(s._live))
            _tracing.round_count("backlog", len(s._pending))
        # step() stays THE call into the session (whoever times the
        # worker wraps it), so what runs between the dispatch's launch
        # and its wait reaches the session as an attribute
        s.in_flight = self._in_flight
        try:
            finished = s.step()
        finally:
            s.in_flight = None
            # what is left: a step path that launched nothing through
            # the hook (dense, beam, speculative), a dispatch that failed
            self._release()
        with _tracing.span("handoff"):
            # while requests wait for a slot, this dispatch's events
            # ride the next launch; with none waiting they go out now
            self._hold = bool(s.pending_requests)
            tokens = self._hand_off(finished)
            self._settle()
        if _tracing.ENABLED:
            _tracing.round_count("tokens", tokens)

    def _hand_off(self, finished):
        """One dispatch's results into the streams' queues. Returns the
        tokens in the events put."""
        s = self._s
        tokens = 0
        # beam streams: one survivor chunk per dispatch (parents +
        # selected tokens + scores + done flags — what a live client
        # renders), the final n-best from the session's bank
        for lane, ev in getattr(s, "last_beam_events", {}).items():
            stream = self._beam_stream.get(lane)
            if stream is None or stream.cancelled.is_set():
                continue
            self._emit(stream, {
                "ok": True, "event": "beam",
                "parents": [int(p) for p in ev["parents"]],
                "tokens": [int(t) for t in ev["tokens"]],
                "scores": [float(x) for x in ev["scores"]],
                "done": [bool(d) for d in ev["done"]]})
            tokens += len(ev["tokens"])
        for lane, fin in getattr(s, "last_finished_beams", {}).items():
            stream = self._beam_stream.pop(lane, None)
            if stream is None:
                continue  # orphaned beam (restored backlog): the
                #           n-best stays banked for take_result claims
            stream.live.clear()
            res = s.take_beam_result(stream.beam_rid)
            if res is None:
                res = fin
            stream.beam_lane = None
            if not stream.cancelled.is_set():
                # the final survivor chunk first (the step that ended
                # the beam still moved tokens), then the n-best
                self._emit(stream, {
                    "ok": True, "event": "beam",
                    "parents": [int(p) for p in fin["parents"]],
                    "tokens": [int(t) for t in fin["step_tokens"]],
                    "scores": [float(x) for x in fin["step_scores"]],
                    "done": [True] * len(fin["parents"])})
                end_ev = {
                    "ok": True, "event": "beam_end",
                    "tokens": [[int(t) for t in row]
                               for row in res["tokens"]],
                    "scores": [float(x) for x in res["scores"]]}
                lp = stream.spec.get("len_penalty")
                if lp is not None:
                    # GNMT length-penalty rescoring as a wire option:
                    # the n-best reorders under the penalized scores;
                    # ``order`` carries the permutation so the client's
                    # survivor-chunk replay cross-check can realign
                    from paddle_tpu.models.transformer import (
                        gnmt_rescore_nbest,
                    )

                    order, toks, pscores = gnmt_rescore_nbest(
                        res["tokens"], res["scores"], s._eos, lp)
                    end_ev["tokens"] = [[int(t) for t in row]
                                        for row in toks]
                    end_ev["scores"] = [float(x) for x in pscores]
                    end_ev["order"] = [int(i) for i in order]
                    end_ev["len_penalty"] = float(lp)
                self._emit(stream, end_ev)
                stream.done = True
                self._emit(stream, {"ok": True, "event": "end"})
                tokens += len(fin["step_tokens"])
        for slot in list(self._slot_stream):
            stream, member = self._slot_stream[slot]
            prev = self._prev_pos[slot]
            if slot in finished:
                toks = self._final_tokens(finished[slot], prev)
                del self._slot_stream[slot]
                del self._prev_pos[slot]
                stream.live.pop(slot, None)
                rid = s._owner.pop(slot, None)  # streamed, not banked
                if rid is not None:
                    s._trace_ids.pop(rid, None)
                if len(toks) and not stream.cancelled.is_set():
                    ev = {"ok": True, "event": "tokens",
                          "member": member,
                          "tokens": [int(t) for t in toks]}
                    if stream.rid is not None:
                        # (rid, seq): seq is the ABSOLUTE trg position
                        # of the chunk's first token — the router/
                        # client splice key (trg[0] is bos, so the
                        # first generated chunk of a prefixless
                        # request carries seq=1)
                        ev["id"] = int(stream.rid)
                        ev["seq"] = int(prev + 1)
                    self._emit(stream, ev)
                    tokens += len(toks)
                if not stream.live and not stream.done:
                    stream.done = True
                    if not stream.cancelled.is_set():
                        end_ev = {"ok": True, "event": "end"}
                        if stream.rid is not None:
                            end_ev["id"] = int(stream.rid)
                        self._emit(stream, end_ev)
            else:
                st = s._live.get(slot)
                if st is None:
                    continue
                new = st["pos"]
                if new > prev and not stream.cancelled.is_set():
                    ev = {"ok": True, "event": "tokens",
                          "member": member,
                          "tokens": [int(t)
                                     for t in st["trg"][prev + 1:new + 1]]}
                    if stream.rid is not None:
                        ev["id"] = int(stream.rid)
                        ev["seq"] = int(prev + 1)
                    self._emit(stream, ev)
                    tokens += new - prev
                self._prev_pos[slot] = new
        # orphaned finishes (no stream — a restored process's backlog):
        # bank exactly like pump(), so take_result can claim them
        for slot, trg in finished.items():
            if slot in self._prev_pos:
                continue
            rid = s._owner.pop(slot, None)
            if rid is not None:
                s._results[rid] = trg
                # a restored process's backlog finishes headless under
                # its ORIGINAL trace id (session-origin continuation):
                # the trace banks with the result, claimable metadata
                # rides take_result
                s._trace_bank(rid)
        return tokens

    def _safe_cancel(self, slots):
        """Session cancel of ``slots`` together (``cancel_many``: one
        table dispatch on a paged Transformer session, bookkeeping on a
        decoder-only one) that can never kill the worker thread: the
        session absorbs repoint failures as recorded leaks; anything
        that still escapes (an invariant break) is logged loudly — a
        dead decode worker wedges EVERY stream, which is strictly
        worse than a few slots in a degraded state."""
        if not slots:
            return
        try:
            self._s.cancel_many(slots)
        except Exception:  # noqa: BLE001 - logged, worker survives
            import logging

            logging.getLogger("paddle_tpu.serving").exception(
                "cancel of slots %s failed during stream teardown",
                slots)

    def _teardown(self, streams):
        """Disconnect/cancel reclamation of ``streams`` as one batch:
        each stream's bookkeeping in turn (a queued request leaves the
        backlog), then every live slot of theirs cancelled by ONE
        session call (slots + page references returned —
        ``pool_conserved`` holds after this)."""
        s = self._s
        slots = []
        for stream in streams:
            stream.done = True
            if stream.beam_lane is not None:
                self._beam_stream.pop(stream.beam_lane, None)
                stream.beam_lane = None
            for slot in list(stream.live):
                self._slot_stream.pop(slot, None)
                self._prev_pos.pop(slot, None)
                # on a beam session the lane's FIRST slot releases the
                # whole lane; its siblings are no longer live by then
                slots.append(slot)
            stream.live.clear()
            if stream.rid is not None:
                s.drop_pending(stream.rid)
                self._rid_stream.pop(stream.rid, None)
                stream.rid = None
        self._safe_cancel(slots)

    def _abort_all(self):
        closed = ServerClosedError("frontend closed before completion")
        streams = list(dict.fromkeys(
            self._tracked_streams() + list(self._rid_stream.values())))
        self._teardown(streams)
        for stream in streams:
            self._emit(stream, error_to_wire(closed))
        self._release()


class ServingFrontend(object):
    """Bind the serving stack to a host/port.

    Parameters
    ----------
    server : serving.server.BatchingServer, optional
        Serves the unary ``predict`` endpoint. The frontend does not
        own it — closing the frontend leaves it (and the session)
        running for in-process use.
    session : serving.generation.SlotDecodeSession, optional
        Serves the streaming ``generate`` endpoint (a dedicated worker
        thread takes ownership of its dispatch loop — don't drive the
        session from other threads while the frontend is up).
    host, port : bind address (port 0 = ephemeral; see ``address``).
    max_stream_backlog : int
        Bound on queued (not yet admitted) solo generate requests;
        beyond it admissions reject with ``QueueFullError``.
    stream_poll_s : float
        Pause before the connection watcher looks again at a streaming
        connection whose readable bytes hold no whole line yet (a
        fragmented cancel). Nothing else runs on a cadence: a cancel
        line or EOF is acted on as it arrives, and a handler thread
        sleeps until its stream has something to write.
    install_signal_handlers : bool
        SIGTERM/SIGINT stop the transport and CHAIN to the previously
        installed handler — install a ``DecodeSnapshotManager``'s
        handlers first and a preempted frontend banks its backlog and
        dies by the signal (the PR 13 discipline, now wire-deep).
    snapshot_manager : serving.snapshot.DecodeSnapshotManager, optional
        Arms the ``snapshot``/``restore``/``attach`` wire endpoints the
        router tier's live-migration protocol uses (docs/SERVING.md
        "Router tier"). Both endpoints execute ON the decode worker at
        a quiesce point; ``restore`` refuses a non-quiesced session
        with the typed retriable ``MigrationBusyError``.
    ssl_context, auth_token :
        Passed through to ``serve_json_lines`` — TLS and bearer auth on
        the frontend's wire (default: both off, wire unchanged).
    """

    def __init__(self, server=None, session=None, host="127.0.0.1",
                 port=0, max_stream_backlog=64, stream_poll_s=0.05,
                 install_signal_handlers=False, snapshot_manager=None,
                 ssl_context=None, auth_token=None):
        if server is None and session is None:
            raise ValueError(
                "ServingFrontend needs a BatchingServer (predict), a "
                "SlotDecodeSession (generate), or both")
        self._batching = server
        self._session = session
        self._snap_mgr = snapshot_manager
        # written by the handlers at the end of a traced request, summed
        # by the decode worker a round
        self._handlers = _HandlerAccount()
        self._decode = (_DecodeWorker(session,
                                      max_backlog=max_stream_backlog,
                                      handlers=self._handlers)
                        if session is not None else None)
        # reads every streaming connection's cancel or EOF, so that the
        # handlers block on their streams' queues alone
        self._watcher = None
        if self._decode is not None:
            self._watcher = _ConnWatcher(
                self._decode.cancel, pause_s=stream_poll_s,
                tls=ssl_context is not None)
            self._decode.watcher = self._watcher
        self._mu = lock_witness.make_lock("serving.frontend.mu")
        self._closed = False
        self._counts = {}
        self._active_streams = 0
        self._conns = 0
        self._io_seen = [0, 0]
        self._prev_handlers = {}
        self._json_server, self.address = serve_json_lines(
            self._dispatch, host=host, port=port, pass_conn=True,
            on_open=self._on_open, on_close=self._on_close,
            ssl_context=ssl_context, auth_token=auth_token)
        if install_signal_handlers:
            self._install_signal_handlers()

    @property
    def port(self):
        return self.address[1]

    # -- connection hooks ----------------------------------------------------

    def _on_open(self, conn):
        with self._mu:
            self._conns += 1
            _fe_active_conns.set(self._conns)

    def _on_close(self, conn):
        # THE disconnect-reclamation hook: whatever streams this
        # connection still owns are torn down on the decode worker —
        # slot freed, page refcounts back to conservation
        for stream in list(conn.state.get("streams", ())):
            if self._decode is not None:
                self._decode.cancel(stream)
        if self._handlers.open:
            self._handlers.closed(conn.id)
        with self._mu:
            self._conns -= 1
            _fe_active_conns.set(self._conns)
        self._sync_io()

    def _sync_io(self):
        srv = self._json_server
        if srv is None:
            return
        with srv._conn_mu:
            sent, received = srv.bytes_sent, srv.bytes_received
        with self._mu:
            ds = sent - self._io_seen[0]
            dr = received - self._io_seen[1]
            self._io_seen = [sent, received]
        if ds > 0:
            _fe_bytes_sent.inc(ds)
        if dr > 0:
            _fe_bytes_received.inc(dr)

    def _observe(self, endpoint, outcome, t0, exemplar=None):
        dt = time.monotonic() - t0
        with self._mu:
            key = (endpoint, outcome)
            self._counts[key] = self._counts.get(key, 0) + 1
        _fe_request_seconds.observe(dt, exemplar=exemplar,
                                    endpoint=endpoint, outcome=outcome)
        if endpoint == "generate":
            _fe_streams_total.inc(outcome=outcome)

    # -- dispatch ------------------------------------------------------------

    def _dispatch(self, req, conn):
        method = req.get("method")
        if method == "predict":
            return self._predict(req)
        if method == "generate":
            return self._generate(req, conn)
        if method == "cancel":
            # out-of-band cancel with no stream in flight on this
            # connection: nothing to tear down, answer idempotently
            return {"ok": True, "event": "cancelled", "idle": True}
        if method == "metrics":
            self._sync_io()
            return {"ok": True, "text": _REGISTRY.to_prometheus()}
        if method == "health":
            return {"ok": True, "health": self._health()}
        if method == "stats":
            return {"ok": True, "stats": self.stats()}
        if method == "take_result":
            return self._take_result(req)
        if method == "attach":
            return self._attach(req, conn)
        if method == "snapshot":
            return self._snapshot(req)
        if method == "restore":
            return self._restore(req)
        if method == "trace":
            # completed-trace lookup by id: ring-resident records only
            # (in-flight ids surface through blackbox dumps instead)
            return {"ok": True,
                    "trace": _tracing.get(str(req.get("id", "")))}
        return error_to_wire(
            ServingError("unknown method %r" % (method,)))

    def _predict(self, req):
        t0 = time.monotonic()
        tr = None
        if _tracing.ENABLED:
            # continue the client-minted trace (or mint a frontend one
            # for traceless callers): covers wire arrival -> batching
            # queue -> dispatch -> response
            tenv = req.get("trace") or {}
            tr = _tracing.start(tenv.get("id"), endpoint="predict",
                                t_client_send=tenv.get("t_send"))
        try:
            if self._batching is None:
                raise ServingError(
                    "this frontend serves no unary predictor")
            if self._closed:
                raise ServerClosedError("frontend is closed")
            wire_in = req.get("inputs")
            if isinstance(wire_in, dict):
                inputs = {k: decode_array(v)
                          for k, v in wire_in.items()}
            else:
                inputs = [decode_array(v) for v in wire_in]
            deadline_s = req.get("deadline_s")
            outs = self._batching.submit(
                inputs, deadline_s=deadline_s,
                trace_id=(tr.id if tr is not None else None)).result()
            resp = {"ok": True,
                    "outputs": [encode_array(np.asarray(o))
                                for o in outs]}
            if tr is not None:
                resp["trace_id"] = tr.id
        except Exception as exc:  # noqa: BLE001 - typed to the wire
            if tr is not None:
                _tracing.finish(tr, outcome=_outcome(exc))
            self._observe("predict", _outcome(exc), t0,
                          exemplar=(tr.id if tr is not None else None))
            return error_to_wire(exc)
        if tr is not None:
            _tracing.finish(tr, outcome="ok")
        self._observe("predict", "ok", t0,
                      exemplar=(tr.id if tr is not None else None))
        return resp

    def _generate(self, req, conn):
        """Streaming dispatch: a GENERATOR the substrate drains line by
        line. Decode-worker messages flow to the socket as produced;
        between messages the handler sleeps on the stream's queue while
        the connection watcher reads the socket for an in-band cancel
        or EOF (``_next_event``); a failed write surfaces as
        ``GeneratorExit`` — every exit path funnels the stream into the
        worker's teardown."""
        t0 = time.monotonic()
        outcome = "error"
        first_token = False
        stream = None
        chunks = 0   # counted with tracing on only
        tr = None
        if _tracing.ENABLED:
            # continue the client-minted trace (or mint one for
            # traceless callers). The root "request" span opened here
            # closes at finish — it covers the whole server-side
            # window, so span coverage vs client wall is the wire RTT
            # plus parse, not an instrumentation lottery
            tenv = req.get("trace") or {}
            tr = _tracing.start(tenv.get("id"), endpoint="generate",
                                t_client_send=tenv.get("t_send"))
        try:
            if self._decode is None:
                self._observe("generate", "error", t0)
                yield error_to_wire(ServingError(
                    "this frontend serves no decode session"))
                return
            if self._closed:
                # observed here: the finally only covers requests that
                # got a stream — and a drain-watching operator needs
                # exactly these post-close rejects in the per-outcome
                # split
                outcome = "closed"
                self._observe("generate", "closed", t0)
                yield error_to_wire(
                    ServerClosedError("frontend is closed"))
                return
            spec = {
                "src": decode_array(req["src"]),
                "src_len": (None if req.get("src_len") is None
                            else int(req["src_len"])),
                "n": int(req.get("n", 1)),
                "prefix": req.get("prefix_tokens"),
                "beam": bool(req.get("beam", False)),
                "len_penalty": (None
                                if req.get("len_penalty") is None
                                else float(req["len_penalty"])),
            }
            if spec["beam"] and spec["n"] != 1:
                self._observe("generate", "error", t0)
                yield error_to_wire(ServingError(
                    "beam=true uses the session's beam_width; it does "
                    "not compose with n > 1 fork groups"))
                return
            if spec["len_penalty"] is not None and not spec["beam"]:
                self._observe("generate", "error", t0)
                yield error_to_wire(ServingError(
                    "len_penalty rescores a beam n-best; it needs "
                    "beam=true"))
                return
            spec["trace_id"] = tr.id if tr is not None else None
            stream = _Stream(spec)
            conn.state.setdefault("streams", set()).add(stream)
            with self._mu:
                self._active_streams += 1
            self._watcher.watch(conn, stream)
            self._decode.submit(stream)
            while True:
                lines, ended = self._next_event(stream, conn)
                for msg in lines:
                    chunk = msg.get("event") in ("tokens", "beam")
                    if chunk and not first_token:
                        first_token = True
                        if tr is not None:
                            tr.mark("first_token")
                        _fe_ttft.observe(
                            time.monotonic() - t0,
                            exemplar=(tr.id if tr is not None else None))
                    if chunk and tr is not None:
                        # the span brackets the substrate's write+flush
                        # of this chunk: t1 lands when the generator
                        # resumes
                        sp = tr.begin("wire.flush",
                                      tokens=len(msg.get("tokens", ())))
                        yield msg
                        tr.end(sp)
                        chunks += 1
                    else:
                        yield msg
                if ended is not None:
                    outcome = ended
                    return
        except GeneratorExit:
            # the substrate closed us: the client's socket died mid-
            # write — tear the generation down, return the capacity
            outcome = "disconnect"
            if stream is not None:
                self._decode.cancel(stream)
            raise
        finally:
            if stream is not None:
                self._watcher.unwatch(conn)  # a failed write gets here
                streams = conn.state.get("streams")
                if streams is not None:
                    streams.discard(stream)
                with self._mu:
                    self._active_streams -= 1
                self._observe("generate", outcome, t0,
                              exemplar=(tr.id if tr is not None
                                        else None))
            if tr is not None:
                # every exit path lands here — cancel, disconnect and
                # error traces close their spans too (finish force-
                # closes stragglers), so the ring never holds a trace
                # with dangling open spans
                _tracing.finish(tr, outcome=outcome)
                self._handlers.request_done(conn.id, chunks)

    def _next_event(self, stream, conn):
        """Sleep until the stream has something to write: ``(lines,
        ended)``, the wire lines to write now and, when they end the
        stream, its outcome (else None). The decode worker's messages
        and the connection watcher's verdict arrive on the one queue,
        so nothing here runs on a timer.

        Before a terminal line is handed out the connection is taken
        out of the watcher: the client may send its next request the
        moment it has read that line, and the watcher must not be the
        one to read it. If the watcher had consumed a cancel line that
        no ``cancelled`` event has answered (the stream ended, or
        failed, while the cancel was in flight), the one ack follows
        the terminal line here, as the substrate's idle-cancel answer
        would have had the line stayed in the socket."""
        while True:
            msg = stream.q.get()
            stream.wakeups += 1
            if msg == _LOOK:
                # TLS: the bytes are read on the thread that writes
                msg = _read_verdict(conn, block=True)
                if msg is None:
                    stream.empty_wakeups += 1
                    self._watcher.rearm(conn)
                    continue
                self._decode.cancel(stream)
            if msg == _CANCEL:
                self._watcher.unwatch(conn)
                return [{"ok": True, "event": "cancelled"}], "cancelled"
            if msg == _EOF:
                self._watcher.unwatch(conn)
                return [], "disconnect"
            if not msg.get("ok", False):
                ended = _outcome(error_from_wire(msg))
            elif msg.get("event") == "end":
                ended = "ok"
            else:
                return [msg], None
            lines = [msg]
            if self._watcher.unwatch(conn):
                lines.append({"ok": True, "event": "cancelled",
                              "idle": True})
            return lines, ended

    def _take_result(self, req):
        t0 = time.monotonic()
        try:
            if self._session is None:
                raise ServingError(
                    "this frontend serves no decode session")
            rid = int(req.get("id", -1))
            # the trace id must be read BEFORE the claim: take_result
            # retires the session's rid->trace binding with the row
            tid = self._session._trace_ids.get(rid)
            tokens = self._session.take_result(rid)
            resp = {"ok": True,
                    "tokens": (None if tokens is None
                               else encode_array(np.asarray(tokens)))}
            if tokens is not None and tid:
                resp["trace_id"] = tid
            if tokens is None:
                # the id may name a BANKED BEAM n-best (the claim id
                # the beam 'admitted' event carried): a beam whose
                # stream died — disconnect, or a preemption that
                # orphaned the lane — finishes headless into the beam
                # result bank, claimable here like solo rows
                beam = self._session.take_beam_result(rid)
                if beam is not None:
                    resp = {"ok": True,
                            "tokens": encode_array(
                                np.asarray(beam["tokens"])),
                            "scores": encode_array(
                                np.asarray(beam["scores"]))}
        except Exception as exc:  # noqa: BLE001 - typed to the wire
            self._observe("take_result", _outcome(exc), t0)
            return error_to_wire(exc)
        self._observe("take_result", "ok", t0)
        return resp

    # -- migration endpoints (router tier) -----------------------------------

    def _attach(self, req, conn):
        """Streaming re-attach to an existing solo request by rid — the
        router's failover/drain splice endpoint. The first event is
        ``resumed`` replaying the request's tokens from absolute
        position 1; after that the stream behaves exactly like
        ``generate`` (the same ``_next_event``, watched for cancel/EOF
        alike, the same teardown discipline)."""
        t0 = time.monotonic()
        outcome = "error"
        stream = None
        try:
            if self._decode is None:
                self._observe("attach", "error", t0)
                yield error_to_wire(ServingError(
                    "this frontend serves no decode session"))
                return
            if self._closed:
                outcome = "closed"
                self._observe("attach", "closed", t0)
                yield error_to_wire(
                    ServerClosedError("frontend is closed"))
                return
            spec = {"attach": int(req["id"]), "n": 1, "prefix": None,
                    "beam": False, "trace_id": None}
            stream = _Stream(spec)
            conn.state.setdefault("streams", set()).add(stream)
            with self._mu:
                self._active_streams += 1
            self._watcher.watch(conn, stream)
            self._decode.submit(stream)
            while True:
                lines, ended = self._next_event(stream, conn)
                for msg in lines:
                    yield msg
                if ended is not None:
                    outcome = ended
                    return
        except GeneratorExit:
            outcome = "disconnect"
            if stream is not None:
                self._decode.cancel(stream)
            raise
        finally:
            if stream is not None:
                self._watcher.unwatch(conn)
                streams = conn.state.get("streams")
                if streams is not None:
                    streams.discard(stream)
                with self._mu:
                    self._active_streams -= 1
                self._observe("attach", outcome, t0)

    def _snapshot(self, req):
        """Quiesced synchronous snapshot with the payload returned ON
        THE WIRE (base64 per file): the router's planned-drain path
        ships it to the target frontend's ``restore``. Executes on the
        decode worker between dispatches — never mid-dispatch."""
        t0 = time.monotonic()
        try:
            if self._snap_mgr is None or self._decode is None:
                raise ServingError(
                    "this frontend has no snapshot manager")
            path = self._decode.call(self._snap_mgr.save)
            files = {}
            for name in sorted(os.listdir(path)):
                with open(os.path.join(path, name), "rb") as f:
                    files[name] = base64.b64encode(
                        f.read()).decode("ascii")
            resp = {"ok": True, "dir": os.path.basename(path),
                    "files": files}
        except Exception as exc:  # noqa: BLE001 - typed to the wire
            self._observe("snapshot", _outcome(exc), t0)
            return error_to_wire(exc)
        self._observe("snapshot", "ok", t0)
        return resp

    def _restore(self, req):
        """Install a SHIPPED snapshot payload into this frontend's
        session — the migration landing. Refuses unless the session is
        fully quiesced (no live slots, no backlog, no tracked streams):
        a restore is a whole-session replace, and landing one on live
        work would destroy it AND break the (seed, slot, position)
        sampling keys migrated streams rely on for bit-exactness. The
        typed ``MigrationBusyError`` is transient BY TYPE, so the
        router's classified retry simply re-asks after the target
        drains."""
        t0 = time.monotonic()
        try:
            mgr = self._snap_mgr
            if mgr is None or self._decode is None:
                raise ServingError(
                    "this frontend has no snapshot manager")
            dirname = os.path.basename(str(req.get("dir", "")))
            if not dirname.startswith("checkpoint_"):
                raise ServingError(
                    "restore needs a checkpoint_<serial> dir name")
            serial = int(dirname.rsplit("_", 1)[-1])
            files = req.get("files") or {}

            def _install():
                w = self._decode
                s = self._session
                if (w._slot_stream or w._beam_stream or w._rid_stream
                        or s.active_slots or s.pending_requests):
                    raise MigrationBusyError(
                        "restore target is not quiesced (live slots, "
                        "backlog or tracked streams present) — drain "
                        "first, then re-ask")
                # join the in-flight async snapshot writer first: this
                # frontend's own periodic save may still be writing a
                # checkpoint whose step-derived serial COLLIDES with
                # the shipped one (two members working the same load
                # reach the same step counts), and installing into the
                # directory it is writing tears both
                mgr.wait()
                step_dir = os.path.join(mgr.checkpoint_dir, dirname)
                if os.path.isdir(step_dir):
                    shutil.rmtree(step_dir)
                os.makedirs(step_dir)
                for name, b64 in files.items():
                    fname = os.path.basename(str(name))
                    with open(os.path.join(step_dir, fname), "wb") as f:
                        f.write(base64.b64decode(b64))
                manifest = mgr.restore(serial=serial)
                if manifest is None:
                    raise ServingError(
                        "shipped snapshot %s failed verification"
                        % dirname)
                return {"ok": True, "serial": int(serial),
                        "live": sorted(int(r)
                                       for r in s._owner.values()),
                        "pending": [int(r)
                                    for r in s.pending_requests],
                        "banked": sorted(int(r) for r in s._results)}

            resp = self._decode.call(_install, timeout=120.0)
        except Exception as exc:  # noqa: BLE001 - typed to the wire
            self._observe("restore", _outcome(exc), t0)
            return error_to_wire(exc)
        self._observe("restore", "ok", t0)
        return resp

    def _health(self):
        out = {}
        if self._batching is not None:
            monitor = self._batching._monitor
            out["server"] = (monitor.state if monitor is not None
                             else "healthy")
        if self._session is not None:
            out["decode"] = self._session.health
        return out

    # -- introspection -------------------------------------------------------

    def stats(self):
        self._sync_io()
        with self._mu:
            by_endpoint = {}
            for (endpoint, outcome), n in sorted(self._counts.items()):
                by_endpoint.setdefault(endpoint, {})[outcome] = n
            out = {
                "requests": by_endpoint,
                "active_connections": self._conns,
                "active_streams": self._active_streams,
                "bytes_sent": self._io_seen[0],
                "bytes_received": self._io_seen[1],
                "closed": self._closed,
            }
        if self._session is not None:
            # the decode-plane view the router polls: quiesce checks
            # before a migration landing, pool conservation after every
            # teardown, and the prefix-cache hit rate the affinity
            # routing exists to preserve. Reads of the session from
            # this (handler) thread are racy-by-design snapshots — the
            # numbers are advisory; the authoritative quiesce check
            # runs ON the worker inside ``restore``.
            s = self._session
            out["decode"] = {
                "active_slots": len(s.active_slots),
                "pending": len(s.pending_requests),
                "free_slots": int(s.free_slots),
                "results_banked": len(s._results),
                "pool_conserved": bool(s.pool_conserved),
                "health": s.health,
                "prefix": s.prefix_cache_stats(),
            }
            # the connection watcher's counts and the handlers'
            # wake-ups (docs/OBSERVABILITY.md)
            out["watcher"] = self._watcher.counts()
        return out

    # -- lifecycle -----------------------------------------------------------

    def close(self, drain=True, timeout=60.0):
        """Stop serving. ``drain=True`` finishes queued + in-flight
        generations (and lets their tails reach the sockets) before
        severing connections; ``drain=False`` cancels live streams and
        fails queued work with ``ServerClosedError``. Does NOT close
        the BatchingServer or the decode session — the frontend is a
        transport layer; its backends outlive it (a SIGTERM'd process
        relies on that: the snapshot manager still owns the session
        after the transport is down)."""
        with self._mu:
            if self._closed and self._json_server is None:
                return
            self._closed = True
        if self._decode is not None:
            self._decode.stop(drain=drain, timeout=timeout)
        if drain:
            # let handler threads flush terminal events before the
            # connections are severed
            deadline = time.monotonic() + min(5.0, timeout)
            while time.monotonic() < deadline:
                with self._mu:
                    if not self._active_streams:
                        break
                time.sleep(0.01)
        if self._watcher is not None:
            # after the drain (a cancel is still read during it); wakes
            # whatever handler is still parked on its stream's queue
            self._watcher.close()
        self._sync_io()
        srv, self._json_server = self._json_server, None
        close_json_server(srv)
        self._uninstall_signal_handlers()

    # -- preemption plumbing -------------------------------------------------

    def _install_signal_handlers(self):
        if threading.current_thread() is not threading.main_thread():
            return
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(
                    sig, self._signal_handler)
            except (ValueError, OSError):
                pass

    def _uninstall_signal_handlers(self):
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError, TypeError):
                pass
        self._prev_handlers = {}

    def _signal_handler(self, signum, frame):
        """Stop the transport, then CHAIN: with a
        ``DecodeSnapshotManager`` installed underneath, the chain banks
        the session (live slots + queued backlog) at the next quiesce
        point and re-raises — the process dies BY the signal with the
        backlog recoverable."""
        # NO lock from signal context: the handler may have interrupted
        # main-thread code HOLDING self._mu (stats()/close()), and a
        # non-reentrant acquire here would deadlock the process short
        # of its snapshot. A bare attribute store is GIL-atomic.
        self._closed = True
        srv = self._json_server
        if srv is not None:
            # shutdown + listener close only: severing live connections
            # takes the connection mutex, which is not safe from signal
            # context; established clients see EOF when the process
            # dies (immediately after the snapshot banks)
            try:
                srv.shutdown()
                srv.server_close()
            except OSError:
                pass
        prev = self._prev_handlers.get(signum)
        if callable(prev):
            prev(signum, frame)
        else:
            # no chained handler: restore the default disposition and
            # die by the signal (the TrainSession discipline)
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        self.close()
        return False
