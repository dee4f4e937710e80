"""Threaded HTTP front end for :class:`ServingEngine`.

Same server shape as distributed/fleet/utils/http_server.py (a
ThreadingHTTPServer on a daemon thread with start/stop), speaking a
minimal JSON generation protocol:

  POST /v1/generate   {"ids": [...], "max_new_tokens"?, "eos_token_id"?,
                       "priority"?, "temperature"?, "top_k"?, "top_p"?,
                       "stop"?, "seed"?, "tenant"?, "json_mode"?,
                       "deadline_ms"?}
                      -> 200 {"id", "output_ids", "generated", "state"}
                         (+ "tenant" echoed when one was named)
                      -> 400 bad request geometry / malformed JSON /
                             invalid decoding params. The documented
                             invalid combinations: temperature < 0,
                             top_k < 0, top_p outside [0, 1],
                             json_mode on an engine constructed
                             without a grammar=, json_mode with
                             speculative decoding enabled
                             (FLAGS_serving_spec_tokens > 0), tenant
                             on an engine without a LoRA pool, and
                             tenant naming an adapter that is not
                             loaded. All defaults (temperature 0 =
                             greedy) reproduce the pre-sampling
                             engine byte-for-byte.
                      -> 429 admission control (queue full / predicted
                             SLO miss / shed at submit — the
                             backpressure signal; Retry-After comes
                             from the engine's predicted-TTFT model,
                             not a fixed idle-wait, so well-behaved
                             clients back off for as long as the
                             backlog actually needs; "reason" in the
                             body says which gate fired)
                      -> 503 request shed by fault policy mid-flight
  GET  /v1/stats      -> 200 the STAT_serving_* counters merged with
                             engine.stats() (TTFT / TPOT percentiles,
                             speculative acceptance rate, per-reason
                             shed counts, slo_attainment when an SLO
                             is configured, per-tenant goodput under
                             "tenants" and the loaded-adapter roster
                             under "lora" once multi-tenant traffic
                             exists)
  GET  /metrics       -> 200 the whole observability registry in
                             Prometheus text exposition format
                             (serving counters/latency histograms,
                             fault counters, XLA compile tracking)
  GET  /health        -> 200 {"ok": true, "slots_free": n, "queued": n,
                              "kv_blocks_free": n, "kv_blocks_used": n}
  GET  /v1/requests/<id>
                      -> 200 the request's span timeline + blame
                             breakdown from the tracing store (marks
                             on the engine clock, per-component
                             milliseconds whose sum reconciles with
                             the measured E2E — see
                             observability/tracing.py)
                      -> 404 unknown id, unsampled request, or one
                             evicted from the bounded finished ring
                             (FLAGS_serving_trace_keep)
  DELETE /v1/requests/<id>
                      -> 200 {"id", "stage", "reason"} — the request
                             was canceled wherever it lived (queued /
                             prefill / handoff / decode) with every
                             KV block and LoRA pin reclaimed
                             (``engine.cancel``; works identically
                             against a ReplicaRouter or DisaggRouter
                             front end)
                      -> 400 non-integer id
                      -> 404 unknown id or already-finished request
                             (double-DELETE is a no-op, not an error)

``deadline_ms`` on POST is the client's patience: the request is
canceled — not completed — wherever it is the moment the deadline
lapses (``Request.hard_deadline``). A client that hangs up early gets
the same treatment: a broken pipe on the response write cancels the
request so a dead connection never pins KV blocks or decode slots.

Like the KV rendezvous server, this is unauthenticated cluster-private
HTTP; bind 127.0.0.1 (the default here) unless the network is trusted.
"""

from __future__ import annotations

import json
import math
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from .. import monitor as _monitor
from .. import observability as _obs
from ..observability import tracing as _tracing
from .engine import QueueFullError, ServingEngine


class _ServingHandler(BaseHTTPRequestHandler):
    server_version = "PaddleTPUServing/1.0"

    def log_message(self, *a):  # quiet
        pass

    def _json(self, code: int, payload: dict,
              headers: Optional[dict] = None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _json_or_cancel(self, code: int, payload: dict, rid: int):
        """Write a response for request ``rid``; a broken pipe means
        the client hung up before the result landed, so cancel the
        request — reclaiming its KV blocks and LoRA pin if it is still
        in flight (a no-op for already-finished requests)."""
        try:
            self._json(code, payload)
        except (BrokenPipeError, ConnectionResetError):
            self.server.engine.cancel(rid, reason="disconnect")

    def do_GET(self):
        engine: ServingEngine = self.server.engine
        if self.path == "/health":
            payload = {"ok": True,
                       "slots_free": engine.cache.num_free,
                       "queued": len(engine._queue),
                       "kv_blocks_free": engine.cache.blocks_free,
                       "kv_blocks_used": engine.cache.blocks_used}
            self._json(200, payload)
        elif self.path == "/v1/stats":
            payload = _monitor.stats_with_prefix("STAT_serving")
            payload.update(engine.stats())
            self._json(200, payload)
        elif self.path == "/metrics":
            body = _obs.prometheus_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path.startswith("/v1/requests/"):
            tail = self.path[len("/v1/requests/"):]
            try:
                rid = int(tail)
            except ValueError:
                self._json(400, {"error": f"bad request id {tail!r}"})
                return
            info = _tracing.get(rid)
            if info is None:
                self._json(404, {"error": f"no trace for request {rid} "
                                          "(unknown, unsampled, or "
                                          "evicted from the ring)"})
            else:
                self._json(200, info)
        else:
            self._json(404, {"error": f"unknown path {self.path!r}"})

    def do_POST(self):
        engine: ServingEngine = self.server.engine
        if self.path != "/v1/generate":
            self._json(404, {"error": f"unknown path {self.path!r}"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            ids = body["ids"]
        except (ValueError, KeyError, TypeError) as e:
            self._json(400, {"error": f"bad request body: {e}"})
            return
        try:
            req = engine.submit(ids,
                                max_new_tokens=body.get("max_new_tokens"),
                                eos_token_id=body.get("eos_token_id"),
                                priority=body.get("priority"),
                                temperature=body.get("temperature"),
                                top_k=body.get("top_k"),
                                top_p=body.get("top_p"),
                                stop=body.get("stop"),
                                seed=body.get("seed"),
                                json_mode=body.get("json_mode"),
                                tenant=body.get("tenant"),
                                deadline_ms=body.get("deadline_ms"))
        except QueueFullError as e:
            # Retry-After: the engine's predicted-TTFT backoff when it
            # attached one (how long the backlog actually needs), else
            # one idle-wait — when the scheduler next looks at the queue
            retry_s = getattr(e, "retry_after_s", None)
            if retry_s is None:
                retry_s = max(1, int(math.ceil(engine.idle_wait)))
            self._json(429, {"error": str(e),
                             "reason": getattr(e, "reason", "queue_full")},
                       headers={"Retry-After": str(int(retry_s))})
            return
        except ValueError as e:
            self._json(400, {"error": str(e)})
            return
        if not req.wait(self.server.request_timeout):
            self._json_or_cancel(
                504, {"error": f"request {req.id} timed out"}, req.id)
            return
        if req.state != "done":
            self._json_or_cancel(
                503, {"error": f"request {req.id} {req.state}: "
                               f"{req.error}"}, req.id)
            return
        payload = {"id": req.id, "output_ids": req.output_ids,
                   "generated": len(req.tokens), "state": req.state}
        if req.tenant:
            payload["tenant"] = req.tenant
        self._json_or_cancel(200, payload, req.id)

    def do_DELETE(self):
        engine: ServingEngine = self.server.engine
        if not self.path.startswith("/v1/requests/"):
            self._json(404, {"error": f"unknown path {self.path!r}"})
            return
        tail = self.path[len("/v1/requests/"):]
        try:
            rid = int(tail)
        except ValueError:
            self._json(400, {"error": f"bad request id {tail!r}"})
            return
        out = engine.cancel(rid, reason="client")
        if out is None:
            self._json(404, {"error": f"request {rid} is unknown or "
                                      "already finished"})
        else:
            self._json(200, out)


class ServingHTTPServer:
    """``srv = ServingHTTPServer(engine); srv.start()`` — starts the
    engine's scheduler thread too, so a constructed server is the whole
    deployment. ``port=0`` binds an ephemeral port (tests)."""

    def __init__(self, engine: ServingEngine, port: int = 0,
                 bind_address: str = "127.0.0.1",
                 request_timeout: float = 120.0):
        self.engine = engine
        self._httpd = ThreadingHTTPServer((bind_address, port),
                                          _ServingHandler)
        self._httpd.engine = engine
        self._httpd.request_timeout = request_timeout
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def start(self):
        self.engine.start()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="serving-http")
        self._thread.start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        self.engine.stop()
