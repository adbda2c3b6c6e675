"""The load generator: a child process that speaks HTTP and nothing else.

    python benchmark/harness/loadgen.py --plan P --out O --host H --port N
        --loop open|closed --workers W --seconds S --timeout T

Standard library only — it imports neither `jax` nor `druid_tpu` (its last
record says so from `sys.modules`), so it cannot hold the chip and measures
from outside the process under test. `W` threads share one cursor over the
plan: each takes the next request, waits until it is due (open loop; a
closed loop's requests are all due at once and `W` is the client count),
POSTs it over its own keep-alive connection and reads the answer to the last
byte. A closed loop takes no new request once the window is over; an open
loop sends its whole plan, which ends with the window. Times are seconds on
this process's monotonic clock from the window's start, whose wall-clock
instant is written first so that the parent can lay them beside its spans.
"""
import argparse
import http.client
import json
import os
import sys
import threading
import time


def run(args) -> int:
    with open(args.plan) as f:
        plan = [json.loads(line) for line in f if line.strip()]
    bodies = [json.dumps(p["query"]).encode() for p in plan]
    keep_dir = os.path.join(os.path.dirname(os.path.abspath(args.out)),
                            "answers")
    os.makedirs(keep_dir, exist_ok=True)
    lock = threading.Lock()
    cursor = [0]
    records = []
    t0 = time.monotonic() + 0.2          # every worker is up before the start
    wall0 = time.time() + (t0 - time.monotonic())

    def worker() -> None:
        conn = None
        while True:
            with lock:
                i = cursor[0]
                if i >= len(plan):
                    return
                if args.loop == "closed" and time.monotonic() - t0 >= args.seconds:
                    return
                cursor[0] = i + 1
            due = t0 + (plan[i]["due_s"] if args.loop == "open" else 0.0)
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            rec = {"i": i, "due_s": due - t0, "status": None, "error": None,
                   "partial": False, "bytes": 0, "kept": None}
            rec["send_s"] = time.monotonic() - t0
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(args.host, args.port,
                                                      timeout=args.timeout)
                conn.request("POST", "/druid/v2", body=bodies[i],
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
                rec["done_s"] = time.monotonic() - t0
                rec["status"] = resp.status
                rec["bytes"] = len(data)
                context = resp.getheader("X-Druid-Response-Context")
                rec["partial"] = bool(context) and (
                    "partial" in context or "missingSegments" in context)
                if plan[i].get("keep") and resp.status == 200:
                    rec["kept"] = os.path.join(keep_dir, f"{i}.json")
                    with open(rec["kept"], "wb") as out:
                        out.write(data)
            except (OSError, http.client.HTTPException) as e:
                rec["done_s"] = time.monotonic() - t0
                rec["error"] = f"{type(e).__name__}: {e}"
                if conn is not None:
                    conn.close()
                conn = None
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=worker, name=f"loadgen-{k}")
               for k in range(args.workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    records.sort(key=lambda r: r["i"])
    with open(args.out + ".tmp", "w") as f:
        f.write(json.dumps({"wall0": wall0, "planned": len(plan),
                            "loop": args.loop, "seconds": args.seconds,
                            "imports_clean": not any(
                                m == "jax" or m.startswith("jax.")
                                or m == "druid_tpu" or m.startswith("druid_tpu.")
                                for m in sys.modules)}) + "\n")
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    os.replace(args.out + ".tmp", args.out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--loop", choices=("open", "closed"), required=True)
    ap.add_argument("--workers", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--timeout", type=float, default=60.0)
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
