"""The HTTP proxy's listen backlog: socketserver's default of 5 resets
connections when callers connect in a burst (a closed loop of 160 clients
opens at once; a decode block ends for many sequences at once). The proxy's
server asks for 1024 (serve/proxy.py:_ProxyServer)."""

import http.client
import threading
from http.server import BaseHTTPRequestHandler

from ray_tpu.serve.proxy import _ProxyServer


def test_a_burst_of_connections_is_all_answered():
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

    assert _ProxyServer.request_queue_size >= 1024
    server = _ProxyServer(("127.0.0.1", 0), Handler)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    n, start, got = 200, threading.Event(), []

    def one():
        start.wait()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("GET", "/")
            got.append(conn.getresponse().read())
        except OSError as e:
            got.append(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=one) for _ in range(n)]
    for t in threads:
        t.start()
    start.set()
    for t in threads:
        t.join()
    server.shutdown()
    server.server_close()
    assert got == [b"ok"] * n
