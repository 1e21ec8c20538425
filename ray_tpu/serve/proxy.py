"""HTTPProxy — the HTTP ingress actor.

Role-equivalent to the reference's per-node proxy (reference:
serve/_private/proxy.py:752 HTTPProxy over uvicorn/starlette ASGI),
rebuilt on the stdlib ThreadingHTTPServer (no external deps):

 - ``/{deployment}[/{method}]``: JSON body in, ``{"result": ...}`` out;
   a body with ``"stream": true`` switches to Server-Sent Events — each
   item the deployment method yields becomes one ``data:`` frame,
   terminated by ``data: [DONE]`` (reference: serve streaming responses
   + the OpenAI SSE contract).
 - ``/v1/completions``: OpenAI-compatible completions routed to the
   deployment named by the body's ``"model"`` field (reference:
   llm/_internal/serve/deployments/routers/router.py).

The gRPC ingress lives in serve/grpc_proxy.py and shares this module's
handle-resolution path (router.HandleCache).
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any


class _ProxyServer(ThreadingHTTPServer):
    """The stdlib server with a listen backlog a serving front end needs.
    socketserver's default is 5: callers that each wait for their reply
    reconnect in bursts (a decode block ends for many sequences at once; a
    closed loop of 160 clients opens at once), the accept queue overflows,
    and the kernel resets connections whose handshake it had already
    completed: ten of 380 requests of one benchmark run ended in
    ConnectionResetError (PERF.md, PR 63). The kernel caps the value at
    net.core.somaxconn."""
    request_queue_size = 1024


class HTTPProxy:
    def __init__(self, controller, port: int = 0):
        from ray_tpu.serve.router import HandleCache
        self._controller = controller
        # shared with the gRPC ingress so the two routing paths can't
        # drift (handle cache + controller liveness probe on miss)
        self._handles = HandleCache(controller)
        proxy = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            def _dispatch(self, body: Any):
                parts = [p for p in self.path.strip("/").split("/") if p]
                stream = isinstance(body, dict) and bool(body.get("stream"))
                # OpenAI-compatible completions + chat completions: the
                # deployment is the body's "model" (reference: serve-LLM
                # router, configs/openai_api_models.py)
                openai = (parts[:2] == ["v1", "completions"]
                          or parts[:3] == ["v1", "chat", "completions"])
                if openai:
                    if not isinstance(body, dict) or "model" not in body:
                        self._reply(400, {"error": "body needs 'model'"})
                        return
                    name = body["model"]
                    base = ("chat_completions" if parts[1] == "chat"
                            else "completions")
                    method = base + ("_stream" if stream else "")
                else:
                    name = parts[0] if parts else ""
                    method = parts[1] if len(parts) > 1 else None
                if not name:
                    self._reply(404, {"error": "no deployment in path"})
                    return
                try:
                    handle = proxy._handle_for(name)
                except KeyError:
                    self._reply(404, {"error": f"no deployment {name!r}"})
                    return
                except Exception as e:  # noqa: BLE001 — controller slow/
                    # unreachable: a JSON 503 beats a dropped connection
                    self._reply(503, {"error": f"routing unavailable: "
                                               f"{e!r}"})
                    return
                try:
                    if method:
                        if method.startswith("_"):
                            raise AttributeError(method)
                        handle = getattr(handle, method)
                except AttributeError:
                    self._reply(404, {"error": f"no method {method!r}"})
                    return
                # model-aware routing tag (reference: proxy reads the
                # serve_multiplexed_model_id header into RequestMetadata)
                mux_id = self.headers.get(
                    "serve_multiplexed_model_id", "") or ""
                try:
                    if stream:
                        gen = handle.options(
                            stream=True,
                            multiplexed_model_id=mux_id).remote(body)
                        self._reply_sse(gen)
                        return
                    if mux_id:
                        handle = handle.options(
                            multiplexed_model_id=mux_id)
                    # client-supplied deadline, same policy as the gRPC
                    # ingress (a cold LLM replica's first compile can
                    # exceed the 60s default on busy hosts); invalid
                    # values are a 400, not a silently-ignored deadline
                    from ray_tpu.serve.router import validate_timeout_s
                    try:
                        timeout_s = validate_timeout_s(
                            body.get("timeout_s")
                            if isinstance(body, dict) else None)
                    except ValueError as e:
                        self._reply(400, {"error": str(e)})
                        return
                    if body is None:
                        resp = handle.remote()
                    else:
                        resp = handle.remote(body)
                    result = resp.result(timeout=timeout_s)
                    # OpenAI clients read top-level id/choices — no wrapper
                    self._reply(200, result if openai
                                else {"result": result})
                except Exception as e:  # noqa: BLE001 — app fault boundary
                    self._reply(500, {"error": repr(e)})

            def _reply_sse(self, gen):
                """Server-Sent Events over chunked transfer: one data:
                frame per yielded item, [DONE] terminator (the OpenAI
                stream framing clients already speak)."""
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(data: bytes) -> None:
                    self.wfile.write(f"{len(data):X}\r\n".encode()
                                     + data + b"\r\n")
                    self.wfile.flush()

                try:
                    for item in gen:
                        try:
                            payload = json.dumps(item)
                        except (TypeError, ValueError):
                            payload = json.dumps({"repr": repr(item)})
                        chunk(f"data: {payload}\n\n".encode())
                    chunk(b"data: [DONE]\n\n")
                except BrokenPipeError:
                    return  # client went away mid-stream
                except Exception as e:  # noqa: BLE001
                    try:
                        chunk(f"data: {json.dumps({'error': repr(e)})}"
                              f"\n\n".encode())
                    except OSError:
                        return
                try:
                    self.wfile.write(b"0\r\n\r\n")  # chunked EOF
                    self.wfile.flush()
                except OSError:
                    pass

            def _reply(self, code: int, payload: dict):
                try:
                    data = json.dumps(payload).encode()
                except (TypeError, ValueError):
                    data = json.dumps(
                        {"result": repr(payload.get("result"))}).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                self._dispatch(None)

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                raw = self.rfile.read(n) if n else b""
                try:
                    body = json.loads(raw) if raw else None
                except json.JSONDecodeError:
                    body = raw.decode("utf-8", "replace")
                self._dispatch(body)

        self._server = _ProxyServer(("127.0.0.1", port), Handler)
        self._port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True, name="serve-http")
        self._thread.start()

    def _handle_for(self, name: str):
        return self._handles.get(name)

    def bound_port(self) -> int:
        return self._port

    def health_check(self) -> bool:
        return True
