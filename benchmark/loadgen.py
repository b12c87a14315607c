"""The load generator: a child process that never imports JAX.

``python -m benchmark.loadgen`` reads one JSON object from standard input
(``url``, ``plan`` as ``traffic.request_plan`` makes it, ``seed``,
``vocab_size``, ``t0`` and ``t_end`` on ``time.monotonic()``, which Linux
shares between processes, and ``timeout_s``), sends the requests over HTTP
and prints one JSON object. ``"records"``: a record for each request with
the instant it was due, was sent and was answered, and for one that was not
answered with 200 the status and the start of the body (status 0: the
exception). ``"holds"``: every instant at which this process's own clock
skipped, as ``[seconds from t0, seconds late]``: a thread that sleeps 10 ms
at a time, from before the pre-roll until the last reply is in, notes each
wake-up that came 0.25 s late or more (``"skip_max_s"`` is the latest of
all its wake-ups, noted or not). The process shares no interpreter and
no lock with the system under test, so such a skip is the host holding
every process, and ``serve_job`` measures a window again that one fell in.

Open loop: a request is sent when it is due whether or not earlier ones
have been answered, and is timed from the instant it was *due*, so a
stalled server cannot hide the wait it imposes on later requests
(``bench_micro.py``'s ``bench_serve`` arithmetic). Closed loop: each client
sends its next request when its reply arrives, and stops at ``t_end``.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

from benchmark import traffic

MAX_IN_FLIGHT = 128   # under the proxy's 200
ERROR_BYTES = 200     # of a refusal's body: enough to name who refused
WATCH_SLEEP_S = 0.01  # the watcher's nap
# a wake-up this late is a hold, and freezes the window it falls in: 0.44 s
# lifted the steady cell's p95 from 138 to 190 ms and 0.82 s to 354, while
# quiet windows skip 0.12 s at most (PERF.md section 6, PRs 36 and 29)
HOLD_S = 0.25


class _Client(threading.local):
    """One keep-alive connection for each sending thread."""
    conn = None


def _post(client: _Client, url: urllib.parse.SplitResult, body: bytes,
          timeout_s: float) -> Dict[str, Any]:
    for attempt in (0, 1):
        if client.conn is None:
            client.conn = http.client.HTTPConnection(
                url.hostname, url.port, timeout=timeout_s)
        try:
            client.conn.request("POST", url.path, body=body, headers={
                "Content-Type": "application/json"})
            resp = client.conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                # the proxy's 503 says who refused: {"error": "..."}
                return {"status": resp.status,
                        "error": data[:ERROR_BYTES].decode("utf-8",
                                                           "replace")}
            return {"status": 200, **json.loads(data)}
        except (http.client.RemoteDisconnected, BrokenPipeError,
                ConnectionResetError) as e:
            # a keep-alive connection the server closed: once more, anew
            client.conn.close()
            client.conn = None
            if attempt:
                return {"status": 0, "error": repr(e)}
        except (OSError, ValueError, http.client.HTTPException) as e:
            client.conn.close()
            client.conn = None
            return {"status": 0, "error": repr(e)}
    raise AssertionError("unreachable")


class Watcher:
    """A thread that naps ``WATCH_SLEEP_S`` at a time and keeps every
    wake-up that came ``HOLD_S`` late or more as ``[when it woke, in
    seconds from t0; how late]``."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.holds: List[List[float]] = []
        self.skip_max_s = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self) -> None:
        last = time.monotonic()
        while not self._stop.is_set():
            time.sleep(WATCH_SLEEP_S)
            now = time.monotonic()
            late = now - last - WATCH_SLEEP_S
            self.skip_max_s = max(self.skip_max_s, late)
            if late >= HOLD_S:
                self.holds.append([now - self.t0, late])
            last = now

    def __enter__(self) -> "Watcher":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


def run(job: Dict[str, Any]) -> Dict[str, Any]:
    with Watcher(job["t0"]) as watcher:
        records = _send_all(job)
    return {"records": records, "holds": watcher.holds,
            "skip_max_s": watcher.skip_max_s}


def _send_all(job: Dict[str, Any]) -> List[Dict[str, Any]]:
    plan, seed, vocab = job["plan"], job["seed"], job["vocab_size"]
    url = urllib.parse.urlsplit(job["url"])
    t0, t_end, timeout_s = job["t0"], job["t_end"], job["timeout_s"]
    lengths: List[int] = plan["lengths"]
    client = _Client()
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()

    def send(index: int, due: float) -> None:
        length = lengths[index % len(lengths)]
        body = json.dumps(traffic.prompt_tokens(
            seed, index, length, vocab)).encode()
        sent = time.monotonic()
        reply = _post(client, url, body, timeout_s)
        done = time.monotonic()
        with lock:
            records.append({"i": index, "len": length, "due": due - t0,
                            "sent": sent - t0, "done": done - t0, **reply})

    if plan["loop"] == "open":
        with ThreadPoolExecutor(MAX_IN_FLIGHT) as pool:
            futures = []
            for index, offset in enumerate(plan["due_s"]):
                due = t0 + offset
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                futures.append(pool.submit(send, index, due))
            for f in futures:
                f.result()
    else:
        n_clients = plan["clients"]

        def client_loop(c: int) -> None:
            index = c
            while time.monotonic() < t_end:
                send(index, time.monotonic())
                index += n_clients

        threads = [threading.Thread(target=client_loop, args=(c,))
                   for c in range(n_clients)]
        start = t0 - plan["preroll_s"]
        delay = start - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    records.sort(key=lambda r: r["i"])
    return records


def main() -> int:
    job = json.load(sys.stdin)
    json.dump(run(job), sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
