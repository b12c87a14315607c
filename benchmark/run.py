"""One cell, once: ``python -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

A new process that owns the cell's chips. It calls ``ray_tpu.init()``
in-process, so trainer workers and serve replicas are threads of the process
that holds the chip and the profiler can trace them; sets up, warms up,
measures for ``--seconds``, checks outputs outside the window and prints one
JSON object as the last line of its standard output. With no TPU, fewer
chips than the cell asks for, or a ``device_kind`` that ``peaks.py`` does
not know, it prints no result and exits with 2. Where something compiled
inside the window or an output disagrees with the reference, the result
says ``"correct": false`` and the exit code is 1.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()   # before anything heavy is imported

import argparse   # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402
from typing import List, Optional   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def set_compile_cache(environ) -> str:
    """JAX's persistent compilation cache: where the environment says, else
    one fixed directory inside the checkout (the path is part of the cache's
    key, so it must not move). Set before anything imports JAX."""
    environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache"))
    # every program of a run, however quick to compile, is found again
    environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return environ["JAX_COMPILATION_CACHE_DIR"]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed is a whole number from 0, --seconds is above 0")
    set_compile_cache(os.environ)
    from benchmark import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_process_start=T_PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
