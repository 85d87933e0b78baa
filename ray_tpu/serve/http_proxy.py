"""HTTP ingress.

Ref analogue: serve/_private/proxy.py ProxyActor (:1097) — the reference
runs uvicorn/ASGI per node; here a threaded stdlib HTTP server in the
driver process routes ``POST /<deployment>`` with a JSON body to the
deployment handle and returns the JSON result. (uvicorn isn't a baked
dependency; the stdlib server keeps ingress dependency-free.)
"""

from __future__ import annotations

import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

from ..core.exceptions import DeadlineExceededError, OverloadedError
from ..util import overload
from .handle import DeploymentHandle


class _ProxyState:
    def __init__(self):
        self.routes: Dict[str, DeploymentHandle] = {}
        self.asgi_routes: set = set()  # route names forwarding raw HTTP


_state = _ProxyState()
_server: Optional[ThreadingHTTPServer] = None
_thread: Optional[threading.Thread] = None


def _make_gate(name: str) -> overload.AdmissionGate:
    """Per-deployment admission gate; sheds map to 503 + Retry-After
    (ref analogue: the proxy's queue-length admission)."""
    from ..core.config import get_config

    return overload.gate_from_config(get_config())


_gates = overload.GateRegistry(_make_gate)


def _request_deadline(headers) -> float:
    """Absolute deadline for one ingress request: an explicit
    ``X-Request-Timeout-S`` budget when the client sent one, else the
    ``serve_default_request_timeout_s`` knob — the single source of
    truth that seeds deadline propagation through handle and replica."""
    from ..core.config import get_config

    default = get_config().serve_default_request_timeout_s
    budget = default
    raw = headers.get("X-Request-Timeout-S")
    if raw:
        try:
            # Clients may only SHORTEN the budget (mirror of the gRPC
            # path): an unclamped header would let one client pin proxy
            # threads and admission slots for arbitrarily long.
            budget = min(default, max(0.001, float(raw)))
        except ValueError:
            pass
    return time.time() + budget


class _Handler(BaseHTTPRequestHandler):
    def log_message(self, *args):  # silence request logging
        pass

    def send_response(self, code, message=None):
        # Remember the status for the request metrics recorded in
        # do_POST's finally — covers the JSON, ASGI, and SSE paths.
        self._obs_status = code
        super().send_response(code, message)
        # Every response names its trace (W3C traceparent), so a
        # user-visible 504/503 correlates to its recorded waterfall
        # (`rtpu trace <id>`) in one hop. ONE site covers the JSON,
        # ASGI, SSE, and overload-shed reply paths.
        trace = getattr(self, "_obs_trace", None)
        if trace is not None:
            from ..core.timeline import format_traceparent

            self.send_header("traceparent",
                             format_traceparent(trace[0], trace[1]))

    def _reply(self, code: int, payload):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        # HEAD responses (incl. errors) must never carry a body — a
        # keep-alive client would parse it as the next response.
        if self.command != "HEAD":
            self.wfile.write(body)

    def do_GET(self):
        if self.path in ("/-/routes", "/-/healthz"):
            # Not a traced request: clear any trace left by an earlier
            # request on this keep-alive connection so the header
            # cannot name a stale waterfall.
            self._obs_trace = None
        if self.path == "/-/routes":
            self._reply(200, sorted(_state.routes))
        elif self.path == "/-/healthz":
            self._reply(200, "ok")
        else:
            self.do_POST()

    def do_PUT(self):  # noqa: N802 — stdlib API
        self.do_POST()

    def do_DELETE(self):  # noqa: N802
        self.do_POST()

    def do_PATCH(self):  # noqa: N802
        self.do_POST()

    def _asgi_forward(self, name: str, handle):
        """Raw HTTP relay to an ASGI deployment (ref: the uvicorn proxy
        path in serve/_private/http_util.py): everything after /<name>
        becomes the app's path; the response passes through verbatim."""
        from urllib.parse import urlparse

        parsed = urlparse(self.path)
        sub = parsed.path[len(name) + 1:] or "/"
        if not sub.startswith("/"):
            sub = "/" + sub
        length = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(length) if length else b""
        request = {
            "method": self.command,
            "path": sub,
            "query_string": (parsed.query or "").encode(),
            "headers": [[k, v] for k, v in self.headers.items()],
            "body": body,
        }
        try:
            # Bounded by the request's remaining deadline budget
            # (installed by _route_request; the config default seeds it).
            resp = handle.options(method="handle_http").remote(
                request
            ).result(timeout=overload.remaining(120.0))
        except OverloadedError as e:
            # Shed downstream (replica limiter / breakers) — counted at
            # its shed site; here it just maps to 503 + Retry-After.
            self._reply_overloaded(e)
            return
        except (DeadlineExceededError, TimeoutError) as e:
            from . import _telemetry

            _telemetry.observe_deadline_exceeded(name, "ingress")
            self._reply(504, {"error": str(e)})
            return
        except Exception as e:  # noqa: BLE001
            self._reply(500, {"error": str(e)})
            return
        body = resp.get("body", b"") or b""
        if isinstance(body, str):
            body = body.encode()
        self.send_response(int(resp.get("status", 200)))
        for k, v in resp.get("headers", []):
            if k.lower() in ("content-length", "connection"):
                continue
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        # A HEAD response carries headers (incl. the Content-Length the
        # GET would have) but MUST NOT carry a body — writing one
        # desynchronizes HTTP keep-alive connections.
        if self.command != "HEAD":
            self.wfile.write(body)

    def _stream_reply(self, handle, arg):
        """Server-sent events: one `data:` frame per item the replica's
        generator yields, flushed as produced (ref analogue: proxy.py
        RESPONSE_STREAMING over ASGI; `curl -N` shows tokens live).
        What the frames cost this thread is added up here and recorded
        when the stream ends (``_telemetry.stream_tally``)."""
        from . import _telemetry

        clock = time.perf_counter
        writes = _telemetry.stream_tally(handle.deployment_name, "write")
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()
        try:
            for item in handle.stream(arg):
                writing = clock()
                self.wfile.write(
                    f"data: {json.dumps(item)}\n\n".encode()
                )
                self.wfile.flush()
                writes.item(clock() - writing)
            self.wfile.write(b"event: end\ndata: null\n\n")
            self.wfile.flush()
        except BrokenPipeError:
            pass  # client went away mid-stream
        except Exception as e:  # noqa: BLE001
            try:
                self.wfile.write(
                    f"event: error\ndata: {json.dumps(str(e))}\n\n".encode()
                )
                self.wfile.flush()
            except Exception:
                pass
        finally:
            writes.flush()

    def do_OPTIONS(self):  # noqa: N802 — stdlib API
        self.do_POST()

    def do_HEAD(self):  # noqa: N802
        self.do_POST()

    def do_POST(self):
        """Instrumented ingress entry (ref analogue: the proxy's request
        span + ray_serve_num_http_requests in serve/_private/proxy.py):
        opens the request's ROOT span — honoring an incoming W3C
        ``traceparent`` so an upstream gateway owns the trace — installs
        it as this thread's context (the handle stamps it onto the task
        spec, the replica parents to it), and records the e2e latency
        histogram + status-code counter on the way out."""
        from urllib.parse import urlparse

        from ..core.timeline import (
            enter_span,
            exit_span,
            get_buffer,
            new_span_id,
            new_trace_id,
            parse_traceparent,
        )
        from . import _telemetry

        name = urlparse(self.path).path.strip("/").split("/")[0]
        parent = parse_traceparent(self.headers.get("traceparent"))
        trace_id = parent[0] if parent else new_trace_id()
        span_id = new_span_id()
        prev = enter_span(trace_id, span_id)
        # Per-request reset: the handler instance is reused across a
        # keep-alive connection, so a request that dies before
        # send_response must not inherit the previous request's status.
        self._obs_status = 500
        self._obs_trace = (trace_id, span_id)
        started = time.time()
        try:
            self._route_request(name)
        finally:
            exit_span(prev)
            ended = time.time()
            code = getattr(self, "_obs_status", 500)
            # Unknown routes record under ONE fixed label: attacker- or
            # crawler-chosen paths must not mint unbounded metric series
            # (the registry never prunes).
            dep_label = (name or "/") if code != 404 else "__unknown__"
            _telemetry.observe_ingress(
                dep_label, "http", code, started, ended,
                trace_id=trace_id,
            )
            try:
                get_buffer().record(
                    f"http:{name or '/'}", started, ended, "",
                    trace_id=trace_id, span_id=span_id,
                    parent_id=parent[1] if parent else "",
                )
            except Exception:
                pass
            # Tail-sampled flight recorder: keep the full record for
            # shed (503), deadline-expired (504), errored, or
            # rolling-p99-slow requests; everything else is dropped.
            from ..util import flight_recorder

            reason = None
            if code == 503:
                reason = "shed"
            elif code == 504:
                reason = "expired"
            elif code >= 500:
                reason = "error"
            flight_recorder.observe_request(
                f"http:{name or '/'}", trace_id, started, ended,
                status=code, reason=reason, surface="http",
            )

    def _route_request(self, name: str):
        from urllib.parse import urlparse

        parts = urlparse(self.path).path.strip("/").split("/")
        streaming = (
            (len(parts) > 1 and parts[1] == "stream")
            or "text/event-stream" in (self.headers.get("Accept") or "")
        )
        handle = _state.routes.get(name)
        if handle is None:
            # Dynamic discovery: any live deployment is routable without
            # explicit registration (ref: the proxy's route table pushed
            # by long-poll — here resolved lazily through the controller
            # and cached, after which the handle long-polls on its own).
            # A stray request must never SPAWN a controller, and a
            # transient controller failure is 503, not 404.
            import ray_tpu

            from . import api as serve_api
            from .controller import CONTROLLER_NAME

            try:
                ray_tpu.get_actor(CONTROLLER_NAME)
            except ValueError:
                self._reply(404, {"error": "serve is not running"})
                return
            try:
                handle = serve_api.get_deployment_handle(name)
                _state.routes[name] = handle
            except KeyError:
                self._reply(404, {"error": f"no deployment {name!r}"})
                return
            except Exception as e:  # noqa: BLE001
                self._reply(503, {"error": f"controller error: {e}"})
                return
        if handle is None:
            self._reply(404, {"error": f"no deployment {name!r}"})
            return
        # Protocol decision follows the ROUTING SNAPSHOT (refreshed by
        # the handle's long-poll), so a redeploy that flips a name
        # between ASGI and JSON is honored without restarting proxies;
        # the explicit-registration set covers driver-local routes.
        is_asgi = (getattr(handle._state, "is_asgi", False)
                   or name in _state.asgi_routes)
        if not is_asgi and self.command in ("HEAD", "OPTIONS"):
            # Non-ASGI deployments speak the JSON envelope only; do NOT
            # execute them on preflight/health probes, and never write a
            # body to a HEAD response (keep-alive desync).
            self.send_response(405)
            self.send_header("Allow", "GET, POST")
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        # ---- overload control: deadline + admission ------------------
        # Shed BEFORE dispatch: past the adaptive concurrency limit and
        # the bounded queue, the request never reaches a handle thread.
        from . import _telemetry

        deadline_ts = _request_deadline(self.headers)
        gate = _gates.get(name)
        try:
            gate.acquire(deadline_ts=deadline_ts)
        except OverloadedError as e:
            _telemetry.observe_shed(name, "proxy")
            self._reply_overloaded(e)
            return
        t0 = time.monotonic()
        prev_dl = overload.set_ambient_deadline(deadline_ts)
        try:
            if is_asgi:
                self._asgi_forward(name, handle)
                return
            length = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(length) if length else b"null"
            try:
                arg = json.loads(raw) if raw else None
            except json.JSONDecodeError:
                self._reply(400, {"error": "invalid JSON body"})
                return
            if streaming:
                # /<name>/<method> routes to that method (e.g.
                # /llm/stream → the deployment's generator endpoint);
                # bare /<name> with an SSE Accept header streams
                # __call__'s result as one event.
                if len(parts) > 1:
                    handle = handle.options(method=parts[1])
                self._stream_reply(handle, arg)
                return
            try:
                result = handle.remote(arg).result(
                    timeout=overload.remaining(60.0)
                )
                self._reply(200, {"result": result})
            except OverloadedError as e:
                # Shed downstream (replica limiter / all breakers open).
                self._reply_overloaded(e)
            except (DeadlineExceededError, TimeoutError) as e:
                _telemetry.observe_deadline_exceeded(name, "ingress")
                self._reply(504, {"error": str(e)})
            except Exception as e:  # noqa: BLE001
                self._reply(500, {"error": str(e)})
        finally:
            overload.set_ambient_deadline(prev_dl)
            code = getattr(self, "_obs_status", 500)
            # Only downstream pushback (503: replica shed / breakers
            # open) shrinks the gate. A 504 means the CLIENT's budget
            # was too small — one client sending tiny X-Request-
            # Timeout-S values must not collapse the shared limit.
            gate.release(time.monotonic() - t0,
                         overloaded=code == 503)

    def _reply_overloaded(self, e: OverloadedError):
        """503 + Retry-After (integer seconds, RFC 9110). The request
        body may be unread at this point: close the connection so a
        keep-alive client cannot desync on the stray bytes."""
        body = json.dumps({"error": str(e)}).encode()
        self.send_response(503)
        retry_after = getattr(e, "retry_after_s", 1.0)
        self.send_header("Retry-After",
                         str(max(1, int(math.ceil(retry_after)))))
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.close_connection = True
        self.end_headers()
        if self.command != "HEAD":
            self.wfile.write(body)


class _TLSHTTPServer(ThreadingHTTPServer):
    """HTTPS ingress with mutual TLS. The LISTENING socket stays plain:
    each connection is wrapped and handshaken in ITS OWN handler thread
    with a timeout — wrapping the listener would run handshakes in the
    single accept loop, letting one stalled client (TCP open, no
    ClientHello) block the whole ingress."""

    _HANDSHAKE_TIMEOUT_S = 10.0

    def __init__(self, addr, handler, tls_ctx):
        self._tls_ctx = tls_ctx
        super().__init__(addr, handler)

    def finish_request(self, request, client_address):
        request.settimeout(self._HANDSHAKE_TIMEOUT_S)
        try:
            request = self._tls_ctx.wrap_socket(
                request, server_side=True,
                do_handshake_on_connect=False,
            )
            request.do_handshake()
        except Exception:
            try:
                request.close()
            except Exception:
                pass
            return
        request.settimeout(None)
        try:
            super().finish_request(request, client_address)
        finally:
            # wrap_socket DETACHED the original fd, so socketserver's
            # shutdown_request/close_request (called with the original
            # socket object) are no-ops — close the wrapped socket
            # explicitly or its fd lives until GC.
            try:
                request.close()
            except Exception:
                pass


def _make_http_server(addr) -> ThreadingHTTPServer:
    """Plain HTTP — or mutual-TLS HTTPS when the cluster runs mTLS
    (plaintext ingress beside an encrypted control plane would be the
    one door left open)."""
    from ..core.tls import server_ssl_context

    ctx = server_ssl_context()
    if ctx is not None:
        return _TLSHTTPServer(addr, _Handler, ctx)
    return ThreadingHTTPServer(addr, _Handler)


def start_proxy(port: int = 8000) -> int:
    global _server, _thread
    if _server is not None:
        return _server.server_address[1]
    _server = _make_http_server(("127.0.0.1", port))
    _thread = threading.Thread(target=_server.serve_forever, daemon=True)
    _thread.start()
    return _server.server_address[1]


def register_route(name: str, handle: DeploymentHandle,
                   *, asgi: bool = False):
    _state.routes[name] = handle
    if asgi:
        _state.asgi_routes.add(name)
    else:
        _state.asgi_routes.discard(name)  # name may be redeployed non-ASGI


def stop_proxy():
    global _server, _thread
    if _server is not None:
        _server.shutdown()
        _server = None
        _thread = None
    _state.routes.clear()
    _state.asgi_routes.clear()
    _gates.clear()


# ---------------------------------------------------------- per-node proxy

class ProxyActor:
    """One HTTP ingress per node (ref: serve/_private/proxy.py ProxyActor
    — the reference runs one proxy on every node so any host serves
    traffic). Runs the same threaded server inside an actor process;
    routes resolve dynamically through the controller."""

    def __init__(self, port: int = 0):
        self._server = _make_http_server(("0.0.0.0", port))
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        self._thread.start()

    def port(self) -> int:
        return self._server.server_address[1]

    def ping(self) -> str:
        return "ok"

    def shutdown(self) -> str:
        self._server.shutdown()
        return "ok"


def start_per_node_actors(actor_cls, port: int,
                          *, timeout: float = 60.0):
    """Launch one ingress actor per alive node (node-affinity pinned)
    and gather their bound ports IN PARALLEL; a node that died since the
    snapshot is skipped after ``timeout`` instead of hanging startup.
    Shared by the HTTP and gRPC per-node proxies."""
    import ray_tpu
    from ray_tpu.core.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    spawned = {}
    for node in ray_tpu.nodes():
        if not node.get("Alive", False):
            continue
        nid = node["NodeID"]
        actor = ray_tpu.remote(
            scheduling_strategy=NodeAffinitySchedulingStrategy(nid),
            max_concurrency=16,
        )(actor_cls).remote(port)
        spawned[nid] = (actor, actor.port.remote())
    proxies = {}
    for nid, (actor, port_ref) in spawned.items():
        try:
            proxies[nid] = (actor, ray_tpu.get(port_ref,
                                               timeout=timeout))
        except Exception:
            try:
                ray_tpu.kill(actor)
            except Exception:
                pass
    return proxies


def start_per_node_proxies(port: int = 8000):
    """Launch one ProxyActor on every alive node; returns
    {node_id: (actor, port)} (ref: proxies on each node serving the
    same route table)."""
    return start_per_node_actors(ProxyActor, port)
