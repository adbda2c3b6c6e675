"""The load generator alone, against a fake server."""
import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

from benchmark.tests.util import BENCH


class _Stalled(BaseHTTPRequestHandler):
    """Answers one request at a time, each after DELAY seconds."""
    protocol_version = "HTTP/1.1"
    delay = 0.1

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(self.delay)
        body = b"[]"
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        if self.path.endswith("/druid/v2") and self.server.partial:
            self.send_header("X-Druid-Response-Context",
                             json.dumps({"partial": True}))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def _serve(partial=False):
    # HTTPServer (not Threading…): one request at a time, a stalled server
    server = HTTPServer(("127.0.0.1", 0), _Stalled)
    server.partial = partial
    t = threading.Thread(target=server.serve_forever)
    t.start()
    return server, t


def _run(tmp_path, server, loop, plan, workers, seconds):
    plan_path = tmp_path / "plan.jsonl"
    plan_path.write_text("".join(json.dumps(p) + "\n" for p in plan))
    out = tmp_path / "records.jsonl"
    subprocess.run(
        [sys.executable, os.path.join(BENCH, "harness", "loadgen.py"),
         "--plan", str(plan_path), "--out", str(out),
         "--port", str(server.server_address[1]), "--loop", loop,
         "--workers", str(workers), "--seconds", str(seconds),
         "--timeout", "30"], check=True, timeout=120)
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    return lines[0], lines[1:]


def test_open_loop_times_from_due_time(tmp_path):
    """20 requests due over 1 s at a server that takes 0.1 s each, one at a
    time: a stall shows up as latency, not as fewer requests."""
    server, thread = _serve()
    try:
        plan = [{"i": i, "due_s": i * 0.05, "query": {"q": i}}
                for i in range(20)]
        header, records = _run(tmp_path, server, "open", plan, 8, 1.0)
    finally:
        server.shutdown()
        thread.join(timeout=10)
        server.server_close()
    assert not thread.is_alive()
    assert header["imports_clean"] is True
    assert [r["i"] for r in records] == list(range(20))      # none dropped
    assert all(r["status"] == 200 for r in records)
    from_due = [r["done_s"] - r["due_s"] for r in records]
    from_send = [r["done_s"] - r["send_s"] for r in records]
    # the server needs 2 s for 1 s of arrivals: the last waited about 1 s
    assert from_due[-1] > 0.8
    assert max(from_due) >= max(from_send)
    # with 8 workers the later requests could not even be sent on time, and
    # the record says so
    assert max(r["send_s"] - r["due_s"] for r in records) > 0.2
    assert all(abs(r["due_s"] - p["due_s"]) < 1e-6
               for r, p in zip(records, plan))


def test_closed_loop_stops_at_the_window_and_sees_partial(tmp_path):
    server, thread = _serve(partial=True)
    try:
        plan = [{"i": i, "due_s": 0.0, "query": {"q": i}, "keep": i == 0}
                for i in range(200)]
        header, records = _run(tmp_path, server, "closed", plan, 2, 1.0)
    finally:
        server.shutdown()
        thread.join(timeout=10)
        server.server_close()
    assert 5 <= len(records) <= 14          # ~10 fit into 1 s, never 200
    assert all(r["send_s"] < 1.0 for r in records)
    assert all(r["partial"] for r in records)
    assert records[0]["kept"] and os.path.exists(records[0]["kept"])
    assert header["planned"] == 200
