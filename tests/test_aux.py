"""Aux subsystems: emitter/monitors, config, query lifecycle, HTTP
endpoints, CLI tools (reference: emitter core, JsonConfigProvider,
QueryLifecycle, QueryResource/SqlResource, DumpSegment)."""
import json
import urllib.request

import numpy as np
import pytest

from druid_tpu.engine import QueryExecutor
from druid_tpu.query.aggregators import CountAggregator
from druid_tpu.query.model import TimeseriesQuery
from druid_tpu.server import QueryHttpServer, QueryLifecycle, RequestLogger
from druid_tpu.server.lifecycle import Unauthorized
from druid_tpu.sql import SqlExecutor
from druid_tpu.utils.config import Config
from druid_tpu.utils.emitter import (BatchingEmitter, CacheMonitor,
                                     ComposingEmitter, Event, FileEmitter,
                                     InMemoryEmitter, MonitorScheduler,
                                     ProcessMonitor, QueryCountStatsMonitor,
                                     ServiceEmitter, SysMonitor)
from tests.conftest import DAY


# ---------------------------------------------------------------------------
# Emitter + monitors
# ---------------------------------------------------------------------------

def test_service_emitter_stamps_dims():
    sink = InMemoryEmitter()
    em = ServiceEmitter("druid-tpu/test", "h1", sink)
    em.metric("query/time", 12.5, dataSource="wiki")
    e = sink.metrics("query/time")[0]
    assert e.dims == {"dataSource": "wiki", "service": "druid-tpu/test",
                      "host": "h1"}
    j = e.to_json()
    assert j["feed"] == "metrics" and j["value"] == 12.5


def test_batching_emitter():
    batches = []
    be = BatchingEmitter(batches.append, batch_size=3)
    try:
        em = ServiceEmitter("s", "h", be)
        for i in range(7):
            em.metric("m", i)
        assert len(batches) == 2 and all(len(b) == 3 for b in batches)
        be.flush()
        assert sum(len(b) for b in batches) == 7
    finally:
        be.close()                 # the flush timer is a real thread


def test_file_emitter(tmp_path):
    path = str(tmp_path / "metrics.log")
    em = ServiceEmitter("s", "h", FileEmitter(path))
    em.metric("a", 1)
    em.metric("b", 2)
    em.flush()
    lines = [json.loads(l) for l in open(path)]
    assert [l["metric"] for l in lines] == ["a", "b"]


def test_monitors_emit():
    sink = InMemoryEmitter()
    em = ServiceEmitter("s", "h", sink)
    qc = QueryCountStatsMonitor()
    qc.on_query(True)
    qc.on_query(False)
    from druid_tpu.cluster import LruCache
    cache = LruCache()
    cache.put("x", "k", 1)
    cache.get("x", "k")
    sched = MonitorScheduler(em, [SysMonitor(), ProcessMonitor(), qc,
                                  CacheMonitor(cache)], 999)
    sched.tick()
    sched.tick()   # SysMonitor cpu needs two samples
    names = {e.metric for e in sink.metrics()}
    assert {"proc/rss", "query/count", "query/success/count",
            "query/cache/total/hits"} <= names
    assert sink.metrics("query/success/count")[0].value == 1


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

def test_config_layers(tmp_path):
    f = tmp_path / "runtime.properties"
    f.write_text("server.port=8082\n# comment\nquery.cache=true\n")
    cfg = Config.load(str(f), env={"DRUID_TPU_SERVER_PORT": "9000"},
                      overrides={"metadata.path": ":memory:"})
    assert cfg.get_int("server.port") == 9000      # env beats file
    assert cfg.get_bool("query.cache")
    assert cfg.get("metadata.path") == ":memory:"


def test_config_json_and_select(tmp_path):
    f = tmp_path / "conf.json"
    f.write_text(json.dumps({"storage": {"type": "local", "dir": "/x"}}))
    cfg = Config.load(str(f), env={})
    assert cfg.get("storage.type") == "local"
    assert cfg.subtree("storage") == {"type": "local", "dir": "/x"}
    made = cfg.select("storage.type",
                      {"local": lambda: "L", "memory": lambda: "M"},
                      default="memory")
    assert made == "L"
    with pytest.raises(ValueError):
        cfg.with_overrides({"storage.type": "bogus"}).select(
            "storage.type", {"local": lambda: 1}, default="local")


# ---------------------------------------------------------------------------
# Query lifecycle
# ---------------------------------------------------------------------------

@pytest.fixture()
def lifecycle_parts(segment):
    sink = InMemoryEmitter()
    em = ServiceEmitter("broker", "h", sink)
    logger = RequestLogger()
    qc = QueryCountStatsMonitor()
    lc = QueryLifecycle(QueryExecutor([segment]), em, logger,
                        authorizer=lambda ident, q: ident != "evil",
                        on_result=qc.on_query)
    return lc, sink, logger, qc


def test_lifecycle_metrics_and_logs(lifecycle_parts, segment):
    lc, sink, logger, qc = lifecycle_parts
    rows = lc.run(TimeseriesQuery.of("test", [DAY], [CountAggregator("n")]))
    assert rows[0]["result"]["n"] == segment.n_rows
    m = sink.metrics("query/time")[0]
    assert m.dims["dataSource"] == "test" and m.dims["success"] == "true"
    assert logger.entries[0]["queryType"] == "timeseries"
    assert logger.entries[0]["success"] is True
    assert qc.success == 1


def test_lifecycle_auth_and_errors(lifecycle_parts):
    lc, sink, logger, qc = lifecycle_parts
    q = TimeseriesQuery.of("test", [DAY], [CountAggregator("n")])
    with pytest.raises(Unauthorized):
        lc.run(q, identity="evil")
    assert logger.entries[-1]["error"] == "unauthorized"
    with pytest.raises(Exception):
        lc.run_json({"queryType": "timeseries", "dataSource": "test",
                     "intervals": [str(DAY)], "granularity": "all",
                     "aggregations": [{"type": "nope", "name": "x"}]})
    assert qc.failed >= 1


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------

def _post(url, payload):
    req = urllib.request.Request(
        url, json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture()
def http_server(segment):
    ex = QueryExecutor([segment])
    lc = QueryLifecycle(ex)
    srv = QueryHttpServer(lc, SqlExecutor(ex), port=0).start()
    yield srv
    srv.stop()


def test_http_native_query(http_server, segment):
    base = f"http://127.0.0.1:{http_server.port}"
    status, rows = _post(f"{base}/druid/v2", {
        "queryType": "timeseries", "dataSource": "test",
        "intervals": [str(DAY)], "granularity": "all",
        "aggregations": [{"type": "count", "name": "n"}]})
    assert status == 200 and rows[0]["result"]["n"] == segment.n_rows


def test_http_sql(http_server, segment):
    base = f"http://127.0.0.1:{http_server.port}"
    status, rows = _post(f"{base}/druid/v2/sql",
                         {"query": "SELECT COUNT(*) n FROM test"})
    assert status == 200 and rows == [{"n": segment.n_rows}]
    status, rows = _post(f"{base}/druid/v2/sql",
                         {"query": "SELECT COUNT(*) FROM test",
                          "resultFormat": "array"})
    assert status == 200 and rows == [[segment.n_rows]]


def test_http_status_and_errors(http_server):
    base = f"http://127.0.0.1:{http_server.port}"
    with urllib.request.urlopen(f"{base}/status") as r:
        assert json.loads(r.read())["version"].startswith("druid-tpu")
    with urllib.request.urlopen(f"{base}/druid/v2/datasources") as r:
        assert json.loads(r.read()) == ["test"]
    status, err = _post(f"{base}/druid/v2", {"queryType": "bogus"})
    assert status == 400 and "error" in err
    status, err = _post(f"{base}/druid/v2/sql", {"query": "SELECT x FROM"})
    assert status == 400


# ---------------------------------------------------------------------------
# CLI tools
# ---------------------------------------------------------------------------

def test_cli_dump_and_validate(tmp_path, segment, capsys):
    from druid_tpu.cli import main
    from druid_tpu.storage.format import persist_segment
    d = str(tmp_path / "seg")
    persist_segment(segment, d)
    assert main(["validate-segment", d]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and f"rows={segment.n_rows}" in out
    assert main(["dump-segment", d, "--full", "--rows", "2"]) == 0
    dump = json.loads(capsys.readouterr().out)
    assert dump["numRows"] == segment.n_rows
    assert dump["columns"]["dimA"]["cardinality"] == \
        segment.dims["dimA"].cardinality
    assert len(dump["rows"]) == 2
    assert main(["version"]) == 0


def test_http_serializes_extension_values(segment):
    import druid_tpu.ext  # noqa: F401
    ex = QueryExecutor([segment])
    srv = QueryHttpServer(QueryLifecycle(ex), port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        status, rows = _post(f"{base}/druid/v2", {
            "queryType": "timeseries", "dataSource": "test",
            "intervals": [str(DAY)], "granularity": "all",
            "aggregations": [
                {"type": "bloom", "name": "b", "fieldName": "dimA"},
                {"type": "approxHistogram", "name": "h",
                 "fieldName": "metLong", "numBuckets": 8,
                 "lowerLimit": 0.0, "upperLimit": 101.0}]})
        assert status == 200
        r = rows[0]["result"]
        assert isinstance(r["b"], str)                  # base64 bloom
        assert sum(r["h"]["counts"]) == segment.n_rows  # structured hist
    finally:
        srv.stop()


def test_variance_field_handling(segment):
    from druid_tpu.ext import VarianceAggregator
    ex = QueryExecutor([segment])
    with pytest.raises(ValueError):
        ex.run(TimeseriesQuery.of("test", [DAY],
                                  [VarianceAggregator("v", "dimA")]))
    rows = ex.run(TimeseriesQuery.of("test", [DAY],
                                     [VarianceAggregator("v", "__time")]))
    t = segment.time_ms.astype(np.float64)
    assert rows[0]["result"]["v"] == pytest.approx(t.var(), rel=1e-9)


def test_config_env_camelcase(tmp_path):
    cfg = Config.load(env={"DRUID_TPU_SERVER_DATANODES": "4"})
    assert cfg.get_int("server.dataNodes", 1) == 4


def test_cli_node_builders_compose_a_cluster(tmp_path, segment):
    """historical (preloading persisted segments from disk) + broker
    (discovering it over /status sync) built exactly as the per-node CLI
    commands build them, then queried over HTTP."""
    import json
    import urllib.request
    from druid_tpu.cli import build_broker, build_historical
    from druid_tpu.storage.format import persist_segment
    seg_dir = tmp_path / "segments" / "s0"
    persist_segment(segment, str(seg_dir))
    node, hist_srv, loaded = build_historical(
        "h0", str(tmp_path / "segments"), port=0)
    assert loaded == 1
    view, broker, http = build_broker([hist_srv.url], port=0)
    try:
        body = json.dumps({
            "queryType": "timeseries", "dataSource": "test",
            "intervals": ["2026-01-01/2026-01-02"], "granularity": "all",
            "aggregations": [{"type": "count", "name": "n"}]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/druid/v2", data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        rows = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert rows[0]["result"]["n"] == segment.n_rows
        # SQL rides the same broker
        sq = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/druid/v2/sql",
            data=json.dumps({"query":
                             "SELECT COUNT(*) c FROM test"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        out = json.loads(urllib.request.urlopen(sq, timeout=60).read())
        assert out[0]["c"] == segment.n_rows
    finally:
        http.stop()
        hist_srv.stop()


def test_cli_validate_rejects_garbage(tmp_path, capsys):
    from druid_tpu.cli import main
    d = tmp_path / "bad"
    d.mkdir()
    (d / "meta.smoosh").write_text("garbage")
    assert main(["validate-segment", str(d)]) == 1


# ---------------------------------------------------------------------------
# Ordered service lifecycle (java-util Lifecycle.java)
# ---------------------------------------------------------------------------

def test_lifecycle_stage_order_and_reverse_stop():
    from druid_tpu.utils.lifecycle import Lifecycle, Stage
    events = []

    def h(name):
        return dict(start=lambda: events.append(f"+{name}"),
                    stop=lambda: events.append(f"-{name}"))

    lc = Lifecycle()
    # registered out of stage order on purpose
    lc.add(**h("announce"), stage=Stage.ANNOUNCEMENTS)
    lc.add(**h("http"), stage=Stage.SERVER)
    lc.add(**h("meta"), stage=Stage.INIT)
    lc.add(**h("monitorA"), stage=Stage.NORMAL)
    lc.add(**h("monitorB"), stage=Stage.NORMAL)
    with lc:
        assert events == ["+meta", "+monitorA", "+monitorB", "+http",
                          "+announce"]
    assert events[5:] == ["-announce", "-http", "-monitorB", "-monitorA",
                          "-meta"]


def test_lifecycle_failed_start_unwinds_started_prefix():
    from druid_tpu.utils.lifecycle import Lifecycle, Stage
    events = []
    lc = Lifecycle()
    lc.add(start=lambda: events.append("+a"),
           stop=lambda: events.append("-a"), stage=Stage.INIT)
    lc.add(start=lambda: (_ for _ in ()).throw(RuntimeError("boom")),
           stop=lambda: events.append("-b"), stage=Stage.NORMAL)
    lc.add(start=lambda: events.append("+c"),
           stop=lambda: events.append("-c"), stage=Stage.SERVER)
    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="boom"):
        lc.start()
    # only the started prefix unwound; the never-started c is untouched
    assert events == ["+a", "-a"]
    assert not lc.running


def test_lifecycle_rejects_late_registration_and_double_start():
    from druid_tpu.utils.lifecycle import Lifecycle
    import pytest as _pytest
    lc = Lifecycle()
    lc.add(start=lambda: None, stop=lambda: None)
    lc.start()
    with _pytest.raises(RuntimeError, match="already started"):
        lc.add(start=lambda: None, stop=lambda: None)
    lc.start()                      # idempotent
    lc.stop()
    lc.stop()                       # idempotent


def test_lifecycle_stop_keeps_going_past_bad_handler():
    from druid_tpu.utils.lifecycle import Lifecycle
    events = []
    lc = Lifecycle()
    lc.add(start=lambda: None, stop=lambda: events.append("-a"))
    lc.add(start=lambda: None,
           stop=lambda: (_ for _ in ()).throw(RuntimeError("bad stop")))
    lc.add(start=lambda: None, stop=lambda: events.append("-c"))
    lc.start()
    lc.stop()
    assert events == ["-c", "-a"]


def test_keepalive_connection_survives_401(segment):
    """HTTP/1.1 keep-alive: a 401 reply must drain the request body, or
    the next request on the same connection parses the stale body as its
    request line."""
    import http.client
    from druid_tpu.server.security import (AuthChain, AuthenticationResult)

    class HeaderGate:
        """Authenticates only requests carrying X-Magic."""
        def authenticate(self, headers):
            if any(k.lower() == "x-magic" for k in headers):
                return AuthenticationResult("alice", "allowAll")
            return None

    ex = QueryExecutor([segment])
    chain = AuthChain(authenticators=[HeaderGate()])
    srv = QueryHttpServer(QueryLifecycle(ex), SqlExecutor(ex),
                          auth_chain=chain, port=0).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port)
        body = json.dumps({"query": "SELECT COUNT(*) FROM test"})
        c.request("POST", "/druid/v2/sql", body,
                  {"Content-Type": "application/json"})
        r1 = c.getresponse()
        assert r1.status == 401
        r1.read()
        # same connection, now authenticated: must succeed, not 400
        c.request("POST", "/druid/v2/sql", body,
                  {"Content-Type": "application/json", "X-Magic": "1"})
        r2 = c.getresponse()
        assert r2.status == 200, r2.status
        assert json.loads(r2.read())[0]["EXPR$0"] == segment.n_rows
    finally:
        srv.stop()


def test_lifecycle_join_blocks_again_after_restart():
    from druid_tpu.utils.lifecycle import Lifecycle
    lc = Lifecycle()
    lc.add(start=lambda: None, stop=lambda: None)
    lc.start()
    lc.stop()
    lc.start()
    assert not lc.join(timeout=0.05)     # must block: not stopped yet
    lc.stop()
    assert lc.join(timeout=0.05)


def test_lifecycle_stop_during_start_leaks_nothing():
    """A stop() racing start() must not leave later-stage handlers running
    forever (the starting thread owns the unwind)."""
    import threading
    import time as _time
    from druid_tpu.utils.lifecycle import Lifecycle, Stage
    events = []
    gate = threading.Event()

    def slow_start():
        events.append("+slow")
        gate.set()
        _time.sleep(0.15)

    lc = Lifecycle()
    lc.add(start=slow_start, stop=lambda: events.append("-slow"),
           stage=Stage.INIT)
    lc.add(start=lambda: events.append("+http"),
           stop=lambda: events.append("-http"), stage=Stage.SERVER)
    t = threading.Thread(target=lc.start)
    t.start()
    gate.wait(2.0)
    lc.stop()               # arrives while slow_start is still running
    t.join(5.0)
    assert not lc.running
    # everything that started was stopped; nothing leaked
    started = {e[1:] for e in events if e.startswith("+")}
    stopped = {e[1:] for e in events if e.startswith("-")}
    assert started == stopped


# ---------------------------------------------------------------------------
# Prioritized query scheduler (PrioritizedExecutorService analog)
# ---------------------------------------------------------------------------

def test_scheduler_priority_order_and_capacity():
    import threading
    import time as _time
    from druid_tpu.server.querymanager import QueryScheduler
    sched = QueryScheduler(total_slots=1)
    assert sched.acquire(priority=0)
    admitted = []

    def waiter(name, prio):
        sched.acquire(priority=prio)
        admitted.append(name)
        sched.release()

    threads = [threading.Thread(target=waiter, args=("low", -1))]
    threads[0].start()
    _time.sleep(0.05)
    threads.append(threading.Thread(target=waiter, args=("high", 10)))
    threads[1].start()
    _time.sleep(0.05)
    assert admitted == []               # slot still held
    sched.release()
    for t in threads:
        t.join(5.0)
    # the later-arriving high-priority query was admitted first
    assert admitted == ["high", "low"]


def test_scheduler_lane_cap_does_not_block_other_lanes():
    from druid_tpu.server.querymanager import QueryScheduler
    sched = QueryScheduler(total_slots=4, lanes={"heavy": 1})
    assert sched.acquire(lane="heavy")
    # heavy lane full: a second heavy query times out...
    assert not sched.acquire(lane="heavy", timeout=0.1)
    # ...but an unlaned query sails through
    assert sched.acquire(timeout=0.1)
    sched.release("heavy")
    assert sched.acquire(lane="heavy", timeout=0.5)


def test_lifecycle_scheduler_admission_timeout(segment):
    from druid_tpu.server.querymanager import (QueryScheduler,
                                               QueryTimeoutError)
    sched = QueryScheduler(total_slots=1)
    lc = QueryLifecycle(QueryExecutor([segment]), scheduler=sched)
    q = TimeseriesQuery.of("test", [DAY], [CountAggregator("n")])
    rows = lc.run(q)
    assert rows[0]["result"]["n"] == segment.n_rows
    # slot freed after the run: a held slot + timeout context -> 504 path
    assert sched.stats()["running"] == 0
    sched.acquire()
    from dataclasses import replace
    q2 = replace(q, context=(("timeout", 100),))
    with pytest.raises(QueryTimeoutError, match="slot"):
        lc.run(q2)
    sched.release()
    assert lc.run(q)[0]["result"]["n"] == segment.n_rows


def test_cancel_while_queued_frees_waiter(segment):
    """DELETE on a query waiting for a slot aborts the wait — it must not
    consume a slot and run later."""
    import threading
    import time as _time
    from druid_tpu.server.querymanager import (QueryInterruptedError,
                                               QueryManager, QueryScheduler)
    sched = QueryScheduler(total_slots=1)
    qm = QueryManager()
    lc = QueryLifecycle(QueryExecutor([segment]), scheduler=sched,
                        query_manager=qm)
    sched.acquire()                      # hold the only slot
    from dataclasses import replace
    q = TimeseriesQuery.of("test", [DAY], [CountAggregator("n")])
    q = replace(q, context=(("queryId", "waiting-q"),))
    errs = []

    def run():
        try:
            lc.run(q)
        except QueryInterruptedError as e:
            errs.append(e)

    t = threading.Thread(target=run)
    t.start()
    _time.sleep(0.2)
    assert lc.cancel("waiting-q")
    t.join(5.0)
    assert errs and "cancelled" in str(errs[0])
    assert sched.stats() == {"running": 1, "waiting": 0}
    sched.release()


def test_scheduler_timeout_budget_is_total(segment):
    """`timeout` covers queue wait + execution: time spent waiting for a
    slot is deducted from the execution deadline."""
    from druid_tpu.server.querymanager import QueryScheduler
    seen = {}

    class Probe:
        def run(self, query):
            seen["timeout"] = query.context_map.get("timeout")
            return []

    import threading
    import time as _time
    sched = QueryScheduler(total_slots=1)
    lc = QueryLifecycle(Probe(), scheduler=sched)
    from dataclasses import replace
    q = TimeseriesQuery.of("test", [DAY], [CountAggregator("n")])
    q = replace(q, context=(("timeout", 5000),))
    sched.acquire()
    t = threading.Thread(target=lambda: lc.run(q))
    t.start()
    _time.sleep(0.4)                     # make it wait ~400ms
    sched.release()
    t.join(5.0)
    assert seen["timeout"] is not None
    assert seen["timeout"] <= 4800       # wait time deducted


def test_query_wait_time_metric(segment):
    from druid_tpu.server.querymanager import QueryScheduler
    sink = InMemoryEmitter()
    em = ServiceEmitter("broker", "h", sink)
    lc = QueryLifecycle(QueryExecutor([segment]), em,
                        scheduler=QueryScheduler(total_slots=2))
    lc.run(TimeseriesQuery.of("test", [DAY], [CountAggregator("n")]))
    waits = sink.metrics("query/wait/time")
    assert waits and waits[0].dims["dataSource"] == "test"


def test_cancel_beats_racing_admission(segment, monkeypatch):
    """A cancel that lands just as a slot frees must win: should_abort is
    consulted before the admission event is honored."""
    from druid_tpu.server.querymanager import (QueryInterruptedError,
                                               QueryScheduler)
    sched = QueryScheduler(total_slots=1)
    sched.acquire()
    cancelled = {"on": False}

    def abort():
        if cancelled["on"]:
            raise QueryInterruptedError("cancelled")

    import threading
    import time as _time
    result = {}

    def waiter():
        try:
            result["ok"] = sched.acquire(should_abort=abort)
        except QueryInterruptedError:
            result["aborted"] = True

    t = threading.Thread(target=waiter)
    t.start()
    _time.sleep(0.15)
    # cancel, THEN free the slot: the waiter must abort, not run
    cancelled["on"] = True
    sched.release()
    t.join(5.0)
    assert result.get("aborted") is True
    # the slot given back by the aborting waiter is acquirable again
    assert sched.acquire(timeout=1.0)
    assert sched.stats()["running"] == 1
    sched.release()


def test_cli_scheduler_config():
    from druid_tpu.cli import _scheduler_from_config
    from druid_tpu.utils.config import Config
    cfg = Config.load(None, env={}, overrides={
        "server.querySlots": "4", "server.lanes": "reports=1,adhoc=2"})
    sched = _scheduler_from_config(cfg)
    assert sched.total_slots == 4
    assert sched.lane_caps == {"reports": 1, "adhoc": 2}
    assert _scheduler_from_config(Config.load(None, env={})) is None


def test_cli_server_subprocess_smoke(tmp_path):
    """`python -m druid_tpu server` brings the whole single-process stack
    up through the staged Lifecycle, serves native + SQL queries, and
    shuts down cleanly on SIGINT. One retry: subprocess jax startup under
    full-suite load can exceed the wait (assertions AND timeout-class
    failures alike)."""
    for attempt in range(2):
        try:
            _run_server_smoke(tmp_path)
            return
        except Exception:
            if attempt == 1:
                raise


def _run_server_smoke(tmp_path):
    import os
    import re as _re
    import signal
    import subprocess
    import sys
    import time as _time
    import urllib.request

    cfg = tmp_path / "runtime.properties"
    cfg.write_text("server.port=0\nmetadata.path=:memory:\n"
                   f"storage.dir={tmp_path}/deep\n"
                   "server.querySlots=4\nserver.lanes=reports=1\n"
                   "coordinator.period=1\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    p = subprocess.Popen(
        [sys.executable, "-m", "druid_tpu", "server", "--config", str(cfg)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        import queue
        import threading
        lines: "queue.Queue[str]" = queue.Queue()

        def pump():
            for ln in p.stdout:
                lines.put(ln)
            lines.put("")                    # EOF marker

        threading.Thread(target=pump, daemon=True).start()
        seen, line = [], ""
        deadline = _time.time() + 300
        while _time.time() < deadline:
            try:
                line = lines.get(timeout=max(0.1, deadline - _time.time()))
            except queue.Empty:
                break
            if line == "":
                break                        # child exited
            seen.append(line)
            if "listening on" in line:
                break
        m = _re.search(r"listening on :(\d+)", line)
        assert m, f"no listen line; child output: {''.join(seen)!r}"
        port = int(m.group(1))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status", timeout=30) as r:
            assert json.loads(r.read())["version"].startswith("druid-tpu")
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/druid/v2/sql",
            json.dumps({"query": "SELECT TABLE_NAME FROM "
                        "INFORMATION_SCHEMA.TABLES"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            json.loads(r.read())            # empty cluster: no tables, 200
        p.send_signal(signal.SIGINT)
        assert p.wait(timeout=30) == 0
    finally:
        if p.poll() is None:
            p.kill()
