"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell — `workloads/<name>.json` on the configuration it names — on
the machine it is started on, and prints one JSON object as the last line of
its standard output: `correct`, `attempted`, `failed`, `metrics`, `device`
and, traced, `breakdown`. It refuses (non-zero exit, no result line) without
a TPU of the cell's chips, or without the program beside it.

This process holds the chip: it deploys one historical and one broker
(`harness/deploy.py`), warms the cell's own templates, then starts the load
generator as a CHILD that imports neither jax nor druid_tpu
(`harness/loadgen.py`) and speaks HTTP to the broker's port. Afterwards it
checks a seeded sample of the window's answers against the plain reference
(`reference/engine.py`) and reduces client records, qtrace spans, counters
and — traced — the profiler's trace to the cell's metrics.

    python3 benchmark/run.py --sweep <name> --rates 2,4,6 [--step-seconds 15]

is a builder's tool outside the contract: one deployment, one step per rate.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)
CACHE = os.path.join(HERE, ".cache")


def log(msg: str) -> None:
    print(msg, flush=True)


def process_start_wall() -> float:
    """Wall-clock instant at which this process started (interpreter
    start-up and imports are set-up too)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    try:
        with open(path) as f:
            spec = json.load(f)
    except OSError:
        raise SystemExit(f"run.py: no {kind}/{name}.json") from None
    if spec.get("name") != name:
        raise SystemExit(f"run.py: {kind}/{name}.json names itself "
                         f"{spec.get('name')!r}")
    return spec


#: what a traffic mix is; a cell that names another workload's `traffic`
#: takes these from that file, letter for letter
TRAFFIC_KEYS = ("loop", "templates", "client_timeout_s", "latency_limit_ms",
                "verify_sample", "trace_seconds")


def load_workload(name: str) -> dict:
    """A cell: `workloads/<name>.json`, its traffic mix resolved."""
    spec = load_json("workloads", name)
    if spec.get("traffic", name) != name:
        mix = load_json("workloads", spec["traffic"])
        spec = dict(spec, **{k: mix[k] for k in TRAFFIC_KEYS if k in mix})
    return spec


# ---------------------------------------------------------------------------
# Requests from this process: warm-up only
# ---------------------------------------------------------------------------

def post_query(port: int, query: dict, timeout: float):
    """(answer, response-context header) of one POST to the broker."""
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/druid/v2", body=json.dumps(query).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {body[:400]!r}")
        return json.loads(body), resp.getheader("X-Druid-Response-Context")
    finally:
        conn.close()


def device_spans(trace: dict):
    return [s for s in (trace or {}).get("spans", [])
            if s.get("name", "").startswith("engine/")
            and s["name"].endswith("/dispatch")]


def warm_up(port: int, plan, data, store, problems: list) -> dict:
    """Every warm-up request once: answer checked against the reference at
    full size, strategy read from its dispatch spans."""
    from benchmark.harness import deploy, verify
    strategies: dict = {}
    for w in plan:
        qid = w["query"]["context"]["queryId"]
        before = deploy.read_counters()["dispatch.total"]
        t0 = time.monotonic()
        # a cold first request pays projection sorts, staging and compiles
        got, context = post_query(port, dict(
            w["query"], context=dict(w["query"]["context"], timeout=900_000)),
            timeout=900.0)
        wall = time.monotonic() - t0
        dispatched = deploy.read_counters()["dispatch.total"] - before
        trace = store.get(qid)
        spans = device_spans(trace)
        ran = sorted({str(s["attrs"].get("strategy")) for s in spans})
        compiles = sum(1 for s in (trace or {}).get("spans", [])
                       if s.get("name") == "engine/compile")
        strategies.setdefault(w["template"], set()).update(ran)
        t1 = time.monotonic()
        wrong = verify.check(data, w["query"], got)
        log(f"warm {qid}: {wall:.2f}s, strategy {ran}, {len(spans)} dispatch "
            f"span(s), {dispatched} dispatch(es), {compiles} compile(s), "
            f"reference {time.monotonic() - t1:.2f}s, "
            f"{'WRONG: ' + wrong if wrong else 'equal'}")
        if wrong:
            problems.append(f"warm-up {qid}: {wrong}")
        if context:
            problems.append(f"warm-up {qid}: response context {context}")
        if w["device"] and dispatched <= 0:
            problems.append(f"warm-up {qid}: no device dispatch for a template "
                            f"that should reach the device")
    return {k: sorted(v) for k, v in strategies.items()}


# ---------------------------------------------------------------------------
# One window of load
# ---------------------------------------------------------------------------

def run_window(port: int, workload: dict, plan, seconds: float, run_dir: str):
    """Start the load generator, wait for it, return (header, records)."""
    loop = workload["loop"]
    plan_path = os.path.join(run_dir, "plan.jsonl")
    out_path = os.path.join(run_dir, "records.jsonl")
    # a sweep runs several windows in one directory
    if os.path.exists(out_path):
        os.remove(out_path)
    shutil.rmtree(os.path.join(run_dir, "answers"), ignore_errors=True)
    with open(plan_path, "w") as f:
        for p in plan:
            f.write(json.dumps(p) + "\n")
    workers = loop["clients"] if loop["kind"] == "closed" else loop["workers"]
    timeout = float(workload["client_timeout_s"])
    cmd = [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
           "--plan", plan_path, "--out", out_path, "--port", str(port),
           "--loop", loop["kind"], "--workers", str(workers),
           "--seconds", str(seconds), "--timeout", str(timeout)]
    child = subprocess.Popen(cmd, cwd=REPO)
    try:
        rc = child.wait(timeout=seconds + 2 * timeout + 30)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        raise RuntimeError("the load generator did not end") from None
    if rc != 0:
        raise RuntimeError(f"the load generator exited with {rc}")
    with open(out_path) as f:
        lines = [json.loads(line) for line in f]
    return lines[0], lines[1:]


def latency_ms(loop_kind: str, rec: dict, timeout_s: float) -> float:
    """A request's latency: from due time (open loop) or send (closed) to
    the last byte; a failed request counts as the client's timeout."""
    if not request_ok(rec):
        return timeout_s * 1000.0
    origin = rec["due_s"] if loop_kind == "open" else rec["send_s"]
    return (rec["done_s"] - origin) * 1000.0


def request_ok(rec: dict) -> bool:
    return rec["status"] == 200 and not rec["partial"] and not rec["error"]


class Profiler(threading.Thread):
    """Traces `length_s` seconds of the window, starting `start_s` in."""

    def __init__(self, directory: str, start_s: float, length_s: float):
        super().__init__(name="profiler")
        self.directory, self.start_s, self.length_s = directory, start_s, length_s
        self.anchor_wall = self.wall0 = self.wall1 = None
        self.error = None

    def run(self) -> None:
        import jax

        from benchmark.harness import xplane
        # device events and TraceMe annotations only: the Python tracer
        # would record every call of a busy server and slow it down
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        time.sleep(self.start_s)
        try:
            jax.profiler.start_trace(self.directory, profiler_options=options)
            try:
                self.anchor_wall = time.time()
                with jax.profiler.TraceAnnotation(xplane.ANCHOR):
                    time.sleep(0.002)
                self.wall0 = time.time()
                time.sleep(self.length_s)
                self.wall1 = time.time()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:   # boundary: the run reports it and fails
            self.error = f"{type(e).__name__}: {e}"


# ---------------------------------------------------------------------------
# A cell
# ---------------------------------------------------------------------------

def prepare(workload: dict, seed: int, stub_device=None):
    """Everything before the window: device check, data, deployment,
    warm-up. Returns a dict of what the window needs."""
    config = load_json("configs", workload["config"])
    # the compile cache at a fixed path inside the checkout (or where the
    # machine says), every program in it after a cell's first run there
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CACHE, "jax"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    try:
        from benchmark.harness import deploy
        import druid_tpu  # noqa: F401  (the program under test)
    except ImportError as e:
        raise SystemExit(f"run.py: the program is not beside the benchmark: {e}")
    from benchmark.harness import traffic
    from benchmark.reference import engine as reference
    try:
        device = stub_device or deploy.require_tpu(int(config["chips"]))
    except deploy.BenchFailure as e:
        raise SystemExit(f"run.py: {e}")
    from druid_tpu import native
    from druid_tpu.obs.trace import trace_store
    native.require()       # the pure-python LZ4 fallback would look like a hang
    log(f"device: {json.dumps(device)}; cell {workload['name']} on "
        f"{config['name']}, seed {seed}")
    t0 = time.monotonic()
    seg_dir, raw_dir, facts = deploy.ensure_data(
        config, seed, os.path.join(CACHE, "data", config["name"]))
    log(f"data: {json.dumps(facts)} ({time.monotonic() - t0:.1f}s; host rss "
        f"{deploy.host_rss_bytes() / 2 ** 30:.1f} GiB)")
    data = reference.RawData(raw_dir, config)
    t0 = time.monotonic()
    deployment = deploy.Deployment(config, seg_dir)
    log(f"serving: {config['segments']} segments loaded, broker on port "
        f"{deployment.port} ({time.monotonic() - t0:.1f}s)")
    problems: list = []
    try:
        strategies = warm_up(deployment.port,
                             traffic.warm_plan(HERE, workload, config, seed),
                             data, trace_store(), problems)
    except BaseException:
        deployment.stop()
        raise
    log(f"strategies: {json.dumps(strategies)}")
    return {"config": config, "device": device, "data": data,
            "deployment": deployment, "problems": problems,
            "strategies": strategies}


def run_cell(args, stub_device=None) -> int:
    start_wall = process_start_wall()
    workload = load_workload(args.workload)
    seed, seconds = int(args.seed), float(args.seconds)
    run_dir = os.path.join(CACHE, "runs", workload["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ctx = prepare(workload, seed, stub_device)
    from benchmark.harness import deploy, layers, traffic, verify, xplane
    import numpy as np
    config, data, deployment = ctx["config"], ctx["data"], ctx["deployment"]
    problems = ctx["problems"]
    loop_kind = workload["loop"]["kind"]
    timeout_s = float(workload["client_timeout_s"])
    try:
        plan = traffic.make_plan(HERE, workload, config, seed, seconds)
        # a seeded sample of the window's answers is kept for the check;
        # drawn from the part of the plan a closed loop is sure to reach
        rng = np.random.default_rng([seed, 0x5A3F1E])
        reach = len(plan) if loop_kind == "open" else \
            max(1, min(len(plan), int(workload["loop"]["sure_requests"])))
        for i in rng.choice(reach, size=min(int(workload["verify_sample"]),
                                            reach), replace=False).tolist():
            plan[i]["keep"] = True
        drain = profiler = None
        if args.trace:
            drain = deploy.TraceDrain(f"bench-{seed}-").start()
            length = min(float(workload.get("trace_seconds", 8)), seconds / 2)
            profiler = Profiler(os.path.join(run_dir, "profile"),
                                max(0.0, (seconds - length) / 2), length)
        counters_before = deploy.read_counters()
        if profiler:
            profiler.start()
        header, records = run_window(deployment.port, workload, plan,
                                     seconds, run_dir)
        setup_s = header["wall0"] - start_wall
        if profiler:
            profiler.join()
        counters_after = deploy.read_counters()
        traces = drain.stop() if drain else {}

        # ---- the window's requests --------------------------------------
        attempted = len(records)
        ok = [r for r in records if request_ok(r)]
        failures: dict = {}
        for r in records:
            if not request_ok(r):
                kind = r["error"].split(":")[0] if r["error"] else (
                    "partial" if r["partial"] else f"HTTP {r['status']}")
                failures[kind] = failures.get(kind, 0) + 1
        wrong = 0
        checked = 0
        t0 = time.monotonic()
        for r in records:
            if r.get("kept"):
                with open(r["kept"]) as f:
                    got = json.load(f)
                diff = verify.check(data, plan[r["i"]]["query"], got)
                checked += 1
                if diff:
                    wrong += 1
                    problems.append(f"request {r['i']} "
                                    f"({plan[r['i']]['template']}): {diff}")
        log(f"checked {checked} in-window answer(s) against the reference, "
            f"{wrong} wrong ({time.monotonic() - t0:.1f}s)")
        failed = attempted - len(ok) + wrong
        if not header["imports_clean"]:
            problems.append("the load generator imported jax or druid_tpu")
        broken = deploy.pallas_broken_reason()
        if broken is not None:
            problems.append(f"Pallas latched off: {broken}")
        if attempted == 0 or not ok:
            problems.append("no request was answered in the window")
        needs_device = any(
            traffic.load_query(HERE, e["query"]).get("device", True)
            for e in workload["templates"])
        dispatched = counters_after["dispatch.total"] - \
            counters_before["dispatch.total"]

        # ---- end-to-end metrics ----------------------------------------
        lat = [latency_ms(loop_kind, r, timeout_s) for r in records]
        late = [(r["send_s"] - r["due_s"]) * 1000.0 for r in records] \
            if loop_kind == "open" and records else [0.0]
        in_window = [r for r in ok if r["done_s"] <= seconds]
        rows = sum(rows_of(data, plan[r["i"]]["query"]) for r in in_window)
        end_to_end = {
            "latency_p50_ms": (statistics.median(lat) if lat else None, "ms"),
            "latency_p95_ms": (layers.percentile(lat, 0.95) if lat else None, "ms"),
            "rows_per_s": (rows / seconds, "rows/s"),
            "setup_s": (setup_s, "s"),
        }
        log(f"window: {attempted} attempted, {len(ok)} answered "
            f"({len(in_window)} inside {seconds:g}s), failures "
            f"{json.dumps(failures)}, planned {header['planned']}, "
            f"generator late p95 {layers.percentile(late, 0.95):.2f} ms / max "
            f"{max(late):.2f} ms, {dispatched} dispatch(es), pool resident "
            f"{counters_after.get('pool.resident_bytes', 0):,.0f} B (evicted "
            f"{counters_after.get('pool.evicted_bytes', 0) - counters_before.get('pool.evicted_bytes', 0):,.0f} B "
            f"in the window), host rss {deploy.host_rss_bytes() / 2 ** 30:.1f} GiB")
        if loop_kind == "closed" and header["planned"] <= attempted:
            problems.append("the closed loop used up its plan: raise plan_qps")

        device = dict(ctx["device"],
                      memory_peak_bytes=deploy.memory_peak_bytes())
        metrics: dict = {}
        result = {"correct": True, "attempted": attempted, "failed": failed,
                  "metrics": metrics, "device": device}
        if not args.trace:
            for name in workload["end_to_end"]:
                value, unit = end_to_end[name]
                if value is not None:
                    metrics[name] = {"value": value, "unit": unit}
        else:
            if profiler.error:
                raise RuntimeError(f"profiler: {profiler.error}")
            found = glob.glob(os.path.join(run_dir, "profile", "plugins",
                                           "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            requests = [{"record": r,
                         "spans": (traces.get(plan[r["i"]]["query"]["context"]
                                              ["queryId"]) or {}).get("spans")}
                        for r in ok]
            every_span = [s for r in requests for s in (r["spans"] or [])]
            reduced = xplane.reduce(xplane.read_planes(found[0]),
                                    profiler.anchor_wall, profiler.wall0,
                                    profiler.wall1, every_span,
                                    require_device=device["platform"] == "tpu")
            # bytes the traced stretch's queries need: each request's bytes
            # by the share of its send-to-done time that lies in the stretch
            lo = profiler.wall0 - header["wall0"]
            hi = profiler.wall1 - header["wall0"]
            needed = 0.0
            for r in ok:
                span = max(r["done_s"] - r["send_s"], 1e-9)
                part = max(0.0, min(r["done_s"], hi) - max(r["send_s"], lo))
                if part > 0:
                    needed += layers.bytes_needed(
                        data, plan[r["i"]]["query"]) * part / span
            peaks = layers.load_peaks(HERE, device["kind"]) \
                if device["platform"] == "tpu" else {"hbm_bytes_per_s": float("nan")}
            reduced["bytes_needed"] = needed
            reduced["peak_bytes_per_s"] = peaks["hbm_bytes_per_s"]
            specs = layers.load_layers(HERE)
            for name in workload["per_layer"]:
                value = layers.evaluate(specs[name], requests, counters_before,
                                        counters_after, reduced)
                if value is not None and value == value:
                    metrics[name] = {"value": value, "unit": specs[name]["unit"]}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            log(f"trace: {len(traces)} request trace(s) drained, "
                f"{reduced['devices']} device plane(s), busy by device "
                f"{reduced['busy_s_by_device']}, traced {reduced['window_s']:.2f}s, "
                f"bytes needed {needed:,.0f}; end-to-end in this traced run "
                f"(not reported): p50 {end_to_end['latency_p50_ms'][0]}")
            if reduced["busy_s"] <= 0 and needs_device and \
                    device["platform"] == "tpu":
                problems.append("no operation ran on the device in the "
                                "traced stretch")
        if needs_device and dispatched <= 0:
            problems.append("no device dispatch in the window")
        for p in problems:
            log(f"PROBLEM: {p}")
        result["correct"] = not problems
    finally:
        deployment.stop()
    print(json.dumps(result), flush=True)
    return 0


_ROWS_CACHE: dict = {}


def rows_of(data, query: dict) -> int:
    from benchmark.reference import engine as reference
    key = json.dumps(query.get("intervals"))
    if key not in _ROWS_CACHE:
        _ROWS_CACHE[key] = reference.rows_scanned(data, query)
    return _ROWS_CACHE[key]


def run_sweep(args) -> int:
    """One deployment, one window per rate: the table the cell's rate is
    chosen from. Each step's plan is the cell's mix at that rate."""
    workload = load_workload(args.sweep)
    if workload["loop"]["kind"] != "open":
        raise SystemExit("run.py: --sweep is for an open-loop workload")
    seed, step = int(args.seed), float(args.step_seconds)
    run_dir = os.path.join(CACHE, "runs", workload["name"] + "-sweep")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ctx = prepare(workload, seed)
    from benchmark.harness import layers, traffic
    timeout_s = float(workload["client_timeout_s"])
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = dict(workload, loop=dict(workload["loop"], rate_qps=rate))
            plan = traffic.make_plan(HERE, w, ctx["config"], seed + k, step)
            _header, records = run_window(ctx["deployment"].port, w, plan,
                                          step, run_dir)
            lat = [latency_ms("open", r, timeout_s) for r in records]
            third = max(1, len(lat) // 3)
            p95 = layers.percentile(lat, 0.95)
            log("sweep " + json.dumps({
                "rate_qps": rate, "requests": len(records),
                "failed": sum(1 for r in records if not request_ok(r)),
                "p50_ms": round(statistics.median(lat), 1),
                "p95_ms": round(p95, 1),
                "max_ms": round(max(lat), 1),
                "mean_first_third_ms": round(statistics.fmean(lat[:third]), 1),
                "mean_last_third_ms": round(statistics.fmean(lat[-third:]), 1),
                "late_p95_ms": round(layers.percentile(
                    [(r["send_s"] - r["due_s"]) * 1000 for r in records], 0.95), 1),
                "drain_s": round(max(r["done_s"] for r in records) - step, 2)}))
            if p95 > float(workload["latency_limit_ms"]):
                break          # past the knee: higher rates only queue longer
    finally:
        ctx["deployment"].stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", help="builder's tool: an open-loop workload")
    ap.add_argument("--rates", default="2,4,6,8")
    ap.add_argument("--step-seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    if args.sweep:
        return run_sweep(args)
    if not args.workload:
        ap.error("--workload is required")
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
