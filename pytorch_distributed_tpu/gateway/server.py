"""HTTP/SSE front door: streaming ingress over the serving fleet.

Round 22 (ROADMAP item 5, the last open half). Every request used to
enter through in-process ``FleetRouter.submit`` calls, so nothing ever
exercised the real front-door semantics a vLLM-style server lives
behind: sockets, token streaming, client disconnects. This module is
that front end — stdlib-only (the PR 8 ``/metrics`` exporter's
``ThreadingHTTPServer`` approach, no new deps):

- ``POST /v1/generate`` — SSE token streaming (``text/event-stream``):
  one ``event: token`` per materialized token, then one ``event: done``
  carrying the request's true outcome + usage. Body is JSON
  ``{"prompt": [token ids], "max_new_tokens": N, "session": S?}``.
- ``GET /v1/health`` — the per-replica health-plane states (PR 17's
  healthy/suspect/dead/draining/rejoining records) + routable count.
- ``GET /metrics`` — Prometheus text: the router's fleet rollup
  (snapshotted on the driver thread — scrapes never race the host
  loop) merged with the gateway's own ``gateway_*`` gauges.

The ingress maps onto the EXISTING control planes instead of inventing
new ones:

- ``X-Deadline-Ms`` header → the PR 17 admission deadline
  (``deadline_s``); a lapsed-at-admission budget sheds through the
  ``SLOGate`` with reason ``deadline-expired`` exactly like an
  in-process submit.
- ``SLOGate`` SHED → HTTP 429 with ``Retry-After`` and the gate's
  reason in a JSON body; SPILL/QUEUE/PREEMPT admit as usual (they are
  backpressure, not failure — the client just sees a slower TTFT).
- client disconnect → ``FleetRouter.cancel(rid)``: a broken pipe on an
  SSE write, or a socket the peer closed while the request was still
  queued (probed with ``select`` + ``MSG_PEEK`` between token waits),
  detaches the stream and queues a cancel for the driver thread. The
  PR 16 cancel path frees the KV blocks and closes the span tree with
  ``outcome=cancelled``; the blocksan disconnect-storm acceptance in
  ``tests/test_gateway.py`` proves zero leaked blocks over real
  sockets.
- malformed input (bad JSON, non-numeric ``X-Deadline-Ms``, a prompt
  the scheduler's admission validator rejects) → 400 with a JSON error
  body — never a stack trace down the socket.

Threading model (``rules_threads``-clean): ONE driver thread owns the
``FleetRouter`` — it drains handler-side ingress/cancel queues, calls
``submit``/``cancel``/``step``, and fans tokens out to bounded,
census-declared per-rid queues (``_Stream.buf``). HTTP handler threads
(spawned by ``ThreadingHTTPServer``) never touch the router; they talk
to the driver exclusively through ``_lock``-guarded queues and wait on
``_wake``. A per-rid queue that overflows (a consumer slower than the
decode tick for ``stream_queue_cap`` tokens) cancels the request —
that is the bounded-backpressure promise the census audits, not a
silent drop. The router's ``on_retire`` hook (fired on the driver
thread, before the final token fans out) closes each stream with its
true outcome, so the terminal SSE event and the span tree always
agree.

    router = FleetRouter(cfg, params, retain_results=False, ...)
    with Gateway(router, port=8000) as gw:
        ...  # curl -N -X POST :8000/v1/generate -d '{"prompt": [1,2]}'

``port=0`` binds an ephemeral port (tests); ``.port`` reports it.
``recipes/serve_lm.py --http-port`` mounts this over the existing
fleet flags; ``scripts/bench_serving.py --http`` drives the heavy-tail
trace through it over real sockets (``serving_http_*``); ANALYSIS.md
"Front door" documents the status-code ↔ gate-ladder mapping.
"""

from __future__ import annotations

import json
import select
import socket
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.telemetry import LatencySeries, prometheus_text
from pytorch_distributed_tpu.telemetry.census import Decl

#: replica states the router will still route to (fleet.router._ROUTABLE
#: re-stated here so /v1/health has no import-order coupling)
_ROUTABLE = ("healthy", "suspect")

_SSE_HEADERS = (
    ("Content-Type", "text/event-stream"),
    ("Cache-Control", "no-cache"),
    ("Connection", "close"),
)


class _Submit:
    """One handler→driver admission request; the handler blocks on
    ``event`` until the driver has routed it through the gate."""

    __slots__ = ("prompt", "max_new", "session", "deadline_s",
                 "event", "rid", "shed_reason", "error", "stream")

    def __init__(self, prompt, max_new, session, deadline_s):
        self.prompt = prompt
        self.max_new = max_new
        self.session = session
        self.deadline_s = deadline_s
        self.event = threading.Event()
        self.rid = -1
        self.shed_reason: Optional[str] = None
        self.error: Optional[str] = None
        self.stream: Optional["_Stream"] = None


class _Stream:
    """Driver→handler token channel for one admitted rid. All fields
    are guarded by the owning Gateway's ``_lock``."""

    __slots__ = ("rid", "prompt_len", "buf", "done", "outcome",
                 "detached", "detach_t", "done_t", "finished",
                 "nbytes", "ttft", "ntok", "deadline_ms")

    def __init__(self, rid: int, prompt_len: int):
        self.rid = rid
        self.prompt_len = prompt_len
        self.buf: deque = deque()
        self.done = False
        self.outcome: Optional[str] = None
        self.detached = False
        self.detach_t: Optional[float] = None
        self.done_t: Optional[float] = None
        self.finished = False  # popped + logged exactly once
        # wire facts stashed by a detaching handler so the driver-side
        # close still writes an honest per-connection record
        self.nbytes = 0
        self.ttft: Optional[float] = None
        self.ntok = 0
        self.deadline_ms = None


def _client_gone(conn) -> bool:
    """True when the peer closed the connection: readable with zero
    bytes on a MSG_PEEK. A streaming client never sends after its
    request body, so readable ⇒ FIN (stray pipelined bytes read as
    alive, which only delays detection to the next write)."""
    try:
        r, _, _ = select.select([conn], [], [], 0)
        if not r:
            return False
        return conn.recv(1, socket.MSG_PEEK) == b""
    except (OSError, ValueError):
        return True


class Gateway:
    """Serve a ``FleetRouter`` over HTTP with SSE token streaming."""

    def __init__(self, router, port: int = 0, host: str = "127.0.0.1", *,
                 metrics_log=None, stream_queue_cap: int = 512,
                 max_pending: int = 4096, max_body_bytes: int = 1 << 20,
                 stream_timeout_s: float = 600.0, poll_s: float = 0.05,
                 idle_sleep_s: float = 0.002, prefix: str = "pdt"):
        self.router = router
        self.metrics_log = metrics_log
        self.stream_queue_cap = int(stream_queue_cap)
        self.max_pending = int(max_pending)
        self.max_body_bytes = int(max_body_bytes)
        self.stream_timeout_s = float(stream_timeout_s)
        self.poll_s = float(poll_s)
        self.idle_sleep_s = float(idle_sleep_s)
        self.prefix = prefix
        self._host = host
        self._requested_port = int(port)
        self.port: Optional[int] = None
        self._server: Optional[ThreadingHTTPServer] = None
        self._http_thread: Optional[threading.Thread] = None
        self._driver: Optional[threading.Thread] = None
        # ---- driver/handler shared state (all under _lock) ----
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._ingress: deque = deque()  # _Submit, handler → driver
        self._cancels: deque = deque()  # (rid, reason), handler → driver
        self._streams: Dict[int, _Stream] = {}
        self._retire_events: deque = deque()  # (rid, outcome, t)
        self._metrics_cache: Dict[str, float] = {}
        self._stop = False
        self._driver_error: Optional[str] = None
        # counters + wire-latency series (all mutated under _lock)
        self._conns = 0
        self._http_400 = 0
        self._http_429 = 0
        self._cancelled_total = 0
        self._completed = 0
        self._bytes_out = 0
        self._worst_gap_s = 0.0
        self.ttft_wire = LatencySeries("ttft_wire")
        self.gap = LatencySeries("gap")
        self.cancel_free = LatencySeries("cancel_free")

    # ---- lifecycle ----

    def start(self) -> "Gateway":
        if self._server is not None:
            return self
        self._refresh_metrics()
        self.router.on_retire = self._on_retire
        gw = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):  # noqa: N802 (stdlib API name)
                if self.path == "/v1/generate":
                    gw._handle_generate(self)
                else:
                    gw._send_json(self, 404, {"error": "not-found"})

            def do_GET(self):  # noqa: N802 (stdlib API name)
                if self.path == "/v1/health":
                    gw._handle_health(self)
                elif self.path in ("/metrics", "/"):
                    gw._handle_metrics(self)
                elif self.path == "/healthz":
                    gw._send_json(self, 200, {"ok": True})
                else:
                    gw._send_json(self, 404, {"error": "not-found"})

            def log_message(self, *args):  # silence per-request stderr
                pass

        self._server = ThreadingHTTPServer(
            (self._host, self._requested_port), Handler
        )
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._http_thread = threading.Thread(
            target=self._server.serve_forever, name="pdt-gateway-http",
            daemon=True,
        )
        self._http_thread.start()
        self._driver = threading.Thread(
            target=self._drive, name="pdt-gateway-driver", daemon=True,
        )
        self._driver.start()
        return self

    def stop(self) -> None:
        """Close the listener, fail queued admissions, end every open
        stream with ``outcome=shutdown``, and join the driver. The
        router is handed back non-drained — callers run the usual
        ``router.drain()`` epilogue (host-work flush + blocksan
        quiesce) themselves."""
        if self._server is None:
            return
        self._server.shutdown()
        self._server.server_close()
        if self._http_thread is not None:
            self._http_thread.join(timeout=5.0)
        with self._lock:
            self._stop = True
            for st in self._streams.values():
                if not st.done:
                    st.done = True
                    st.outcome = st.outcome or "shutdown"
                    st.done_t = time.perf_counter()
            self._wake.notify_all()
        if self._driver is not None:
            self._driver.join(timeout=30.0)
        self.router.on_retire = None
        self._server = None
        self._http_thread = None
        self._driver = None

    def __enter__(self) -> "Gateway":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- the driver thread: sole owner of the router ----

    def _drive(self) -> None:
        try:
            self._drive_loop()
        except Exception as e:  # noqa: BLE001 — the front door must
            # not wedge its handler threads on a router bug: fail every
            # open stream/queued admission loudly instead
            with self._lock:
                self._driver_error = repr(e)
                for st in self._streams.values():
                    if not st.done:
                        st.done = True
                        st.outcome = "error"
                        st.done_t = time.perf_counter()
                for sub in self._ingress:
                    sub.error = f"gateway driver failed: {e!r}"
                    sub.event.set()
                self._ingress.clear()
                self._wake.notify_all()

    def _drive_loop(self) -> None:
        n = 0
        while True:
            with self._lock:
                subs = list(self._ingress)
                self._ingress.clear()
                cancels = list(self._cancels)
                self._cancels.clear()
                stopping = self._stop
            for rid, reason in cancels:
                # synchronous: the PR 16 path frees blocks and fires the
                # retire hook (→ _retire_events) before this returns;
                # False = already terminal, idempotently nothing to do
                self.router.cancel(rid, reason=reason)
            for sub in subs:
                if stopping:
                    sub.error = "gateway shutting down"
                    sub.event.set()
                else:
                    self._admit(sub)
            busy = not self.router.idle
            out = self.router.step() if busy and not stopping else []
            self._deliver(out)
            if stopping and not subs and not cancels:
                break
            n += 1
            if n % 64 == 0:
                self._refresh_metrics()
            if not busy and not subs:
                time.sleep(self.idle_sleep_s)

    def _admit(self, sub: _Submit) -> None:
        """Route one handler admission through the gate. Runs on the
        driver thread; the shed contract is synchronous (a shed rid is
        in ``router.rejected`` when ``submit`` returns), so the waiting
        handler learns its 429 here, not from a poll."""
        try:
            rid = self.router.submit(
                np.asarray(sub.prompt, dtype=np.int32), sub.max_new,
                session=sub.session, deadline_s=sub.deadline_s,
            )
        except ValueError as e:
            # the scheduler's admission validator (empty prompt, prompt
            # past max_seq_len, budget overflow) — a client error
            sub.error = str(e)
            sub.event.set()
            return
        reason = self.router.rejected.get(rid)
        if reason is not None:
            sub.rid = rid
            sub.shed_reason = reason
            sub.event.set()
            return
        st = _Stream(rid, prompt_len=len(sub.prompt))
        with self._lock:
            self._streams[rid] = st
        sub.rid = rid
        sub.stream = st
        sub.event.set()

    def _on_retire(self, rid: int, outcome: str) -> None:
        """FleetRouter.on_retire hook — driver thread, mid-step."""
        with self._lock:
            self._retire_events.append((rid, outcome, time.perf_counter()))

    def _deliver(self, out: List[Tuple[int, int]]) -> None:
        """Fan this step's tokens out to their streams, then apply the
        step's retire events (tokens first: the retire hook fires
        mid-collect, before the final token reaches ``out``)."""
        overflowed: List[int] = []
        with self._lock:
            for rid, tok in out:
                st = self._streams.get(rid)
                if st is None or st.done:
                    continue
                if len(st.buf) >= self.stream_queue_cap:
                    if rid not in overflowed:
                        overflowed.append(rid)
                    continue
                st.buf.append(int(tok))
            retired = False
            while self._retire_events:
                rid, outcome, t = self._retire_events.popleft()
                st = self._streams.get(rid)
                if st is None:
                    continue
                retired = True
                if not st.done or (st.detached
                                   and st.outcome == "shutdown"):
                    st.done = True
                    st.outcome = outcome
                    st.done_t = t
                if st.detached:
                    # no handler will ever write the terminal event —
                    # close the books here (cancel-to-block-free lands
                    # in the latency series the bench quotes)
                    self._finish_detached_locked(st)
            if out or overflowed or retired:
                self._wake.notify_all()
        for rid in overflowed:
            # bounded-backpressure promise: a consumer slower than the
            # decode tick for stream_queue_cap tokens is cancelled, so
            # neither host memory nor KV blocks wait on a stuck socket
            self.router.cancel(rid, reason="slow-consumer")

    def _refresh_metrics(self) -> None:
        """Snapshot the fleet rollup on the driver thread so ``/metrics``
        scrapes never race the host loop."""
        try:
            snap = self.router.metrics()
        except Exception:  # noqa: BLE001 — a scrape cache refresh must
            return  # never kill the driver; the stale snapshot stands
        flat = {k: v for k, v in snap.items()
                if isinstance(v, (int, float, bool))}
        with self._lock:
            self._metrics_cache = flat

    # ---- stream bookkeeping (lock held where noted) ----

    def _finish_detached_locked(self, st: _Stream) -> None:
        if st.finished:
            return
        st.finished = True
        self._streams.pop(st.rid, None)
        if st.outcome == "cancelled":
            self._cancelled_total += 1  # jaxlint: disable=thread-unsynced-mutation -- _locked suffix: every caller (_deliver, stop) holds self._lock
            if st.detach_t is not None and st.done_t is not None:
                self.cancel_free.observe(max(st.done_t - st.detach_t, 0.0))
        self._log_http_locked(
            rid=st.rid, route="/v1/generate", status=200,
            deadline=st.deadline_ms, disconnect=True, nbytes=st.nbytes,
            ttft_wire=st.ttft, outcome=st.outcome, tokens=st.ntok,
            gap_max_ms=None,
        )

    def _finish_conn(self, st: _Stream, *, deadline_ms, nbytes: int,
                     ttft: Optional[float], ntok: int,
                     gaps: List[float]) -> None:
        """Handler-side normal completion: terminal event written."""
        with self._lock:
            if st.finished:
                return
            st.finished = True
            self._streams.pop(st.rid, None)
            self._completed += 1
            self._bytes_out += nbytes
            if ttft is not None:
                self.ttft_wire.observe(ttft)
            gap_max = 0.0
            for g in gaps:
                self.gap.observe(g)
                gap_max = max(gap_max, g)
            if gap_max > self._worst_gap_s:
                self._worst_gap_s = gap_max
            self._log_http_locked(
                rid=st.rid, route="/v1/generate", status=200,
                deadline=deadline_ms, disconnect=False, nbytes=nbytes,
                ttft_wire=ttft, outcome=st.outcome, tokens=ntok,
                gap_max_ms=round(gap_max * 1e3, 3) if gaps else None,
            )

    def _detach(self, st: _Stream, *, deadline_ms, nbytes: int,
                ttft: Optional[float], ntok: int, reason: str) -> None:
        """Handler-side disconnect: hand the rid to the driver for
        cancellation and stop touching the socket."""
        with self._lock:
            if st.finished:
                return
            self._bytes_out += nbytes
            if ttft is not None:
                self.ttft_wire.observe(ttft)
            if st.done:
                # raced its own retirement — nothing left to cancel
                st.finished = True
                self._streams.pop(st.rid, None)
                self._log_http_locked(
                    rid=st.rid, route="/v1/generate", status=200,
                    deadline=deadline_ms, disconnect=True, nbytes=nbytes,
                    ttft_wire=ttft, outcome=st.outcome, tokens=ntok,
                    gap_max_ms=None,
                )
                return
            st.detached = True
            st.detach_t = time.perf_counter()
            st.nbytes = nbytes
            st.ttft = ttft
            st.ntok = ntok
            st.deadline_ms = deadline_ms
            self._cancels.append((st.rid, reason))

    def _log_http_locked(self, *, rid: int, route: str, status: int,
                         deadline, disconnect: bool, nbytes: int,
                         ttft_wire: Optional[float], outcome=None,
                         tokens: Optional[int] = None, reason=None,
                         gap_max_ms=None) -> None:
        self._conns += 1  # jaxlint: disable=thread-unsynced-mutation -- _locked suffix: every caller holds self._lock (handlers via _finish_conn/_detach/_reject, driver via _deliver)
        if self.metrics_log is None:
            return
        self.metrics_log.log(
            kind="http", rid=rid, route=route, status=status,
            deadline=deadline, disconnect=bool(disconnect), bytes=nbytes,
            ttft_wire=(round(ttft_wire, 6)
                       if ttft_wire is not None else None),
            outcome=outcome, tokens=tokens, reason=reason,
            gap_max_ms=gap_max_ms,
            open=len(self._streams), queued=len(self._ingress),
        )

    # ---- HTTP handlers (ThreadingHTTPServer threads) ----

    def _send_json(self, h, status: int, body: dict,
                   headers: Tuple[Tuple[str, str], ...] = ()) -> None:
        payload = json.dumps(body).encode()
        try:
            h.send_response(status)
            h.send_header("Content-Type", "application/json")
            h.send_header("Content-Length", str(len(payload)))
            for k, v in headers:
                h.send_header(k, v)
            h.end_headers()
            h.wfile.write(payload)
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass  # peer gone before the error body landed — nothing owed

    def _reject(self, h, status: int, body: dict, *, route: str,
                rid: int = -1, deadline=None,
                headers: Tuple[Tuple[str, str], ...] = ()) -> None:
        with self._lock:
            if status == 400:
                self._http_400 += 1
            elif status == 429:
                self._http_429 += 1
            self._log_http_locked(
                rid=rid, route=route, status=status, deadline=deadline,
                disconnect=False, nbytes=0, ttft_wire=None,
                reason=body.get("reason") or body.get("error"),
            )
        self._send_json(h, status, body, headers)

    def _read_request(self, h):
        """(payload, deadline_ms, error_response) — error_response is a
        (status, body) pair when the request is malformed."""
        try:
            length = int(h.headers.get("Content-Length", ""))
        except ValueError:
            return None, None, (400, {"error": "missing-length"})
        if length > self.max_body_bytes:
            return None, None, (413, {"error": "body-too-large",
                                      "limit": self.max_body_bytes})
        try:
            raw = h.rfile.read(length)
            payload = json.loads(raw)
        except (ValueError, OSError):
            return None, None, (400, {"error": "bad-json"})
        if not isinstance(payload, dict):
            return None, None, (400, {"error": "bad-json"})
        deadline_ms = None
        header = h.headers.get("X-Deadline-Ms")
        if header is not None:
            try:
                deadline_ms = float(header)
            except ValueError:
                return None, None, (
                    400, {"error": "bad-deadline",
                          "detail": "X-Deadline-Ms must be numeric"})
        prompt = payload.get("prompt")
        if (not isinstance(prompt, list) or not prompt
                or not all(isinstance(t, int) and not isinstance(t, bool)
                           for t in prompt)):
            return None, None, (
                400, {"error": "bad-prompt",
                      "detail": "prompt must be a non-empty list of "
                                "token ids"})
        max_new = payload.get("max_new_tokens", 16)
        if not isinstance(max_new, int) or isinstance(max_new, bool) \
                or max_new < 1:
            return None, None, (
                400, {"error": "bad-max-new-tokens",
                      "detail": "max_new_tokens must be a positive int"})
        session = payload.get("session")
        if session is not None and not isinstance(session, int):
            return None, None, (
                400, {"error": "bad-session",
                      "detail": "session must be an int"})
        return (prompt, max_new, session), deadline_ms, None

    def _handle_generate(self, h) -> None:
        t0 = time.perf_counter()
        route = "/v1/generate"
        parsed, deadline_ms, err = self._read_request(h)
        if err is not None:
            self._reject(h, err[0], err[1], route=route,
                         deadline=deadline_ms)
            return
        prompt, max_new, session = parsed
        deadline_s = deadline_ms / 1e3 if deadline_ms is not None else None
        sub = _Submit(prompt, max_new, session, deadline_s)
        with self._lock:
            if self._stop or self._driver_error is not None:
                err = (503, {"error": "unavailable",
                             "detail": self._driver_error or "shutting down"})
            elif len(self._ingress) >= self.max_pending:
                err = (503, {"error": "overloaded"})
            else:
                err = None
                self._ingress.append(sub)
        if err is not None:
            self._reject(h, err[0], err[1], route=route,
                         deadline=deadline_ms)
            return
        if not sub.event.wait(timeout=30.0):
            self._reject(h, 503, {"error": "admission-timeout"},
                         route=route, deadline=deadline_ms)
            return
        if sub.error is not None:
            self._reject(h, 400, {"error": "invalid-request",
                                  "detail": sub.error},
                         route=route, deadline=deadline_ms)
            return
        if sub.shed_reason is not None:
            # the SLOGate ladder's SHED rung in HTTP: explicit, with a
            # hint to come back — reason strings are the gate's own
            # (queue_depth / slo_* / deadline-expired / draining / ...)
            self._reject(
                h, 429, {"error": "shed", "reason": sub.shed_reason,
                         "rid": sub.rid},
                route=route, rid=sub.rid, deadline=deadline_ms,
                headers=(("Retry-After", "1"),),
            )
            return
        self._stream_sse(h, sub, t0, deadline_ms)

    def _stream_sse(self, h, sub: _Submit, t0: float,
                    deadline_ms) -> None:
        st = sub.stream
        ttft: Optional[float] = None
        nbytes = 0
        ntok = 0
        last_t: Optional[float] = None
        gaps: List[float] = []
        give_up = t0 + self.stream_timeout_s
        try:
            h.send_response(200)
            for k, v in _SSE_HEADERS:
                h.send_header(k, v)
            h.end_headers()
            while True:
                with self._lock:
                    if not st.buf and not st.done:
                        self._wake.wait(timeout=self.poll_s)
                    toks = list(st.buf)
                    st.buf.clear()
                    done, outcome = st.done, st.outcome
                if toks:
                    now = time.perf_counter()
                    if ttft is None:
                        ttft = now - t0
                    elif last_t is not None:
                        gaps.append(now - last_t)
                    last_t = now
                    for tok in toks:
                        data = json.dumps({"i": ntok, "token": tok})
                        chunk = f"event: token\ndata: {data}\n\n".encode()
                        h.wfile.write(chunk)
                        nbytes += len(chunk)
                        ntok += 1
                    h.wfile.flush()
                if done and not st.buf:
                    data = json.dumps({
                        "rid": st.rid, "outcome": outcome,
                        "usage": {"prompt_tokens": st.prompt_len,
                                  "completion_tokens": ntok},
                    })
                    chunk = f"event: done\ndata: {data}\n\n".encode()
                    h.wfile.write(chunk)
                    h.wfile.flush()
                    nbytes += len(chunk)
                    self._finish_conn(st, deadline_ms=deadline_ms,
                                      nbytes=nbytes, ttft=ttft, ntok=ntok,
                                      gaps=gaps)
                    return
                if not toks and _client_gone(h.connection):
                    self._detach(st, deadline_ms=deadline_ms,
                                 nbytes=nbytes, ttft=ttft, ntok=ntok,
                                 reason="client-disconnect")
                    return
                if time.perf_counter() > give_up:
                    self._detach(st, deadline_ms=deadline_ms,
                                 nbytes=nbytes, ttft=ttft, ntok=ntok,
                                 reason="stream-timeout")
                    return
        except (BrokenPipeError, ConnectionResetError, OSError):
            # mid-stream disconnect: the write raised, the blocks must
            # not wait for a reader that is gone
            self._detach(st, deadline_ms=deadline_ms, nbytes=nbytes,
                         ttft=ttft, ntok=ntok, reason="client-disconnect")
        except Exception:  # noqa: BLE001 — a handler bug must neither
            # leak the stream entry nor write a stack trace down the
            # socket; the cancel path reclaims the blocks
            self._detach(st, deadline_ms=deadline_ms, nbytes=nbytes,
                         ttft=ttft, ntok=ntok, reason="handler-error")

    def _handle_health(self, h) -> None:
        replicas = [dict(rec, replica=i)
                    for i, rec in enumerate(self.router.health)]
        routable = sum(1 for r in replicas if r["state"] in _ROUTABLE)
        self._send_json(h, 200, {
            "replicas": replicas, "routable": routable,
            "total": len(replicas),
        })

    def _handle_metrics(self, h) -> None:
        body = prometheus_text(self.metrics(), prefix=self.prefix).encode()
        try:
            h.send_response(200)
            h.send_header("Content-Type", "text/plain; version=0.0.4")
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            h.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass

    # ---- metrics + census ----

    def metrics(self) -> dict:
        """Fleet rollup (driver-thread snapshot) + ``gateway_*`` gauges."""
        with self._lock:
            out = dict(self._metrics_cache)
            out.update({
                "gateway_open_streams": len(self._streams),
                "gateway_queued": len(self._ingress),
                "gateway_connections": self._conns,
                "gateway_completed": self._completed,
                "gateway_http_400": self._http_400,
                "gateway_http_429": self._http_429,
                "gateway_cancels": self._cancelled_total,
                "gateway_bytes_out": self._bytes_out,
                "gateway_worst_gap_ms": round(self._worst_gap_s * 1e3, 3),
            })
            out.update(self.ttft_wire.summary("gateway_ttft_wire"))
            out.update(self.gap.summary("gateway_gap"))
            out.update(self.cancel_free.summary("gateway_cancel_free"))
        return out

    def census_decls(self):
        """Round 21 contract: every long-lived container on the gateway
        declares its bound (telemetry/census.py)."""
        return [
            Decl("_ingress", "fixed", cap=lambda g: g.max_pending,
                 why="handler→driver admissions; each entry is a blocked "
                     "HTTP thread, refused past max_pending (503)"),
            Decl("_cancels", "fixed", cap=lambda g: g.max_pending,
                 why="handler→driver cancel requests; at most one per "
                     "open connection, drained every driver loop"),
            Decl("_streams", "live", per_live=1, why=(
                "one bounded token queue per in-flight HTTP request; "
                "popped at terminal write, or by the driver when a "
                "detached rid retires")),
            Decl("_retire_events", "fixed", cap=16384,
                 why="terminal transitions queued for end-of-step "
                     "delivery; drained every _deliver call"),
            Decl("_metrics_cache", "fixed", cap=512,
                 why="one flat scalar snapshot of router.metrics(), "
                     "replaced (never grown) each refresh"),
            Decl("ttft_wire.values", "fixed",
                 cap=lambda g: 2 * g.ttft_wire.window,
                 why="LatencySeries percentile window"),
            Decl("gap.values", "fixed", cap=lambda g: 2 * g.gap.window,
                 why="LatencySeries percentile window"),
            Decl("cancel_free.values", "fixed",
                 cap=lambda g: 2 * g.cancel_free.window,
                 why="LatencySeries percentile window"),
        ]

    def census_owners(self):
        """Swept (name, object) pairs — the gateway itself; the router
        and its replicas publish their own owner set."""
        return [("gateway", self)]
