"""The load generator of a generating cell: a child process that never
imports JAX (``benchmark/loadgen.py`` posts a bare prompt and cannot carry an
answer's length; this one posts ``{"prompt": [...], "max_new_tokens": n}``).

``python -m benchmark.generate_loadgen`` reads one JSON object from standard
input (``url``, ``plan`` as ``generate_job.request_plan`` makes it, ``seed``,
``vocab_size``, ``t0`` and ``t_end`` on ``time.monotonic()``, ``timeout_s``),
runs the plan's closed loop and prints one JSON object, as ``loadgen`` does:
``"records"`` (a record a request: its index, its prompt's and its answer's
length, when it was sent and answered, and the reply's ``tokens`` and
``logits`` or the refusal), ``"holds"`` and ``"skip_max_s"`` (``loadgen``'s
own watcher of this process's clock). The connection, the watcher and the
clients' loop are ``loadgen``'s, imported.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.parse
from typing import Any, Dict, List

from benchmark import traffic
from benchmark.loadgen import Watcher, _Client, _post


def _send_all(job: Dict[str, Any]) -> List[Dict[str, Any]]:
    plan, seed, vocab = job["plan"], job["seed"], job["vocab_size"]
    url = urllib.parse.urlsplit(job["url"])
    t0, t_end, timeout_s = job["t0"], job["t_end"], job["timeout_s"]
    lengths, answers = plan["lengths"], plan["answers"]
    n_clients = plan["clients"]
    client = _Client()
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()

    def send(index: int) -> None:
        at = index % len(lengths)
        body = json.dumps({
            "prompt": traffic.prompt_tokens(seed, index, lengths[at], vocab),
            "max_new_tokens": answers[at]}).encode()
        sent = time.monotonic()
        reply = _post(client, url, body, timeout_s)
        done = time.monotonic()
        with lock:
            records.append({"i": index, "len": lengths[at],
                            "n_new": answers[at], "due": sent - t0,
                            "sent": sent - t0, "done": done - t0, **reply})

    def client_loop(c: int) -> None:
        index = c
        while time.monotonic() < t_end:
            send(index)
            index += n_clients

    start = t0 - plan["preroll_s"]

    def client_from_start(c: int) -> None:
        time.sleep(max(0.0, start - time.monotonic()))
        client_loop(c)

    threads = [threading.Thread(target=client_from_start, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    records.sort(key=lambda r: r["i"])
    return records


def run(job: Dict[str, Any]) -> Dict[str, Any]:
    if job["plan"]["loop"] != "closed":
        raise ValueError("a generating cell's loop is closed")
    with Watcher(job["t0"]) as watcher:
        records = _send_all(job)
    return {"records": records, "holds": watcher.holds,
            "skip_max_s": watcher.skip_max_s}


def main() -> int:
    job = json.load(sys.stdin)
    json.dump(run(job), sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
