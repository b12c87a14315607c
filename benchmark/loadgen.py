"""The load generator: a child process that never imports JAX.

``python -m benchmark.loadgen`` reads one JSON object from standard input
(``url``, ``plan`` as ``traffic.request_plan`` makes it, ``seed``,
``vocab_size``, ``t0`` and ``t_end`` on ``time.monotonic()``, which Linux
shares between processes, and ``timeout_s``), sends the requests over HTTP
and prints one JSON object: a record for each request with the instant it
was due, was sent and was answered.

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
                return {"status": resp.status}
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


def run(job: Dict[str, Any]) -> Dict[str, Any]:
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
    return {"records": records}


def main() -> int:
    job = json.load(sys.stdin)
    json.dump(run(job), sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
