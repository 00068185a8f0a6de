"""Open-loop HTTP load generator. Runs as a child process and never imports
jax: the parent holds the chip.

Reads a plan (pickle, written by the parent): the URL, the payloads, and a
schedule of (seconds after start, payload index, keep the answer?). Sends
each request at its due time whatever happened to the earlier ones, from a
pool of worker threads, and writes one record per request: when it was due,
when it left, when the last byte came, the status, and the body where the
plan asked to keep it. A non-200, a timeout or a dropped connection is a
failed request: status 0 and no latency.
"""

import http.client
import pickle
import queue
import sys
import threading
import time
from urllib.parse import urlparse


def _worker(jobs, plan, host, port, t0, records):
    while True:
        job = jobs.get()
        if job is None:
            return
        i, due, payload_index, keep = job
        sent = time.perf_counter() - t0
        status, body, done = 0, None, None
        try:
            conn = http.client.HTTPConnection(host, port, timeout=plan["timeout_s"])
            try:
                conn.request(
                    "POST", "/invocations", body=plan["payloads"][payload_index],
                    headers={"Content-Type": "text/csv", "Accept": "text/csv"},
                )
                resp = conn.getresponse()
                data = resp.read()
                done = time.perf_counter() - t0
                status = resp.status
                body = data if keep else None
            finally:
                conn.close()
        except (OSError, http.client.HTTPException):
            status = 0
        records[i] = (due, sent, done, status, payload_index, body)


def main(plan_path, result_path):
    with open(plan_path, "rb") as f:
        plan = pickle.load(f)
    url = urlparse(plan["url"])
    schedule = plan["schedule"]
    records = [None] * len(schedule)
    jobs = queue.Queue()
    t0 = time.perf_counter() + 0.2
    workers = [
        threading.Thread(
            target=_worker, args=(jobs, plan, url.hostname, url.port, t0, records), daemon=True
        )
        for _ in range(int(plan["workers"]))
    ]
    for w in workers:
        w.start()
    for i, (due, payload_index, keep) in enumerate(schedule):
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        jobs.put((i, due, payload_index, keep))
    for _ in workers:
        jobs.put(None)
    deadline = time.perf_counter() + plan["timeout_s"] + 1.0
    for w in workers:
        w.join(max(0.0, deadline - time.perf_counter()))
    with open(result_path, "wb") as f:
        pickle.dump({"records": records, "wall_s": time.perf_counter() - t0}, f)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
