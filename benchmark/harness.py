"""What every cell's run shares: the device check, the window's marks in
the profiler's trace, the reduction to per-layer metrics and the one JSON
line."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, Optional

from benchmark import manifest as manifest_mod
from benchmark import peaks

SPAN_PREFIX = "bench."               # marks the benchmark's own host spans
WINDOW_SPAN = "window"               # the span that marks the window
EXIT_OK, EXIT_INCORRECT, EXIT_NO_DEVICE = 0, 1, 2


class NoDevice(RuntimeError):
    """The cell cannot be measured on the devices JAX found."""


@dataclasses.dataclass
class Env:
    """What a job is told about the run it is part of."""
    cell: manifest_mod.Cell
    seed: int
    seconds: float
    trace: bool
    on_tpu: bool              # False only under the tests' entry
    trace_dir: Optional[str] = None


@dataclasses.dataclass
class Outcome:
    """What a job hands back."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]          # without setup_s
    t_first_measured: float               # time.monotonic()
    counters: Dict[str, Any]              # what reducers read besides a trace
    memory_peak_bytes: int
    notes: List[str] = dataclasses.field(default_factory=list)


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces). The
    prefix is how the reduction tells the benchmark's spans from the
    runtime's own."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def transformer_config(dims: Dict[str, Any], seq_len: int,
                       opts: Dict[str, Any]):
    """The program's model configuration for a cell's sizes (``dims`` as
    ``manifest.model_dims`` gives them) and its ``model`` options."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_ff=dims["d_ff"],
        max_seq_len=seq_len, dtype=jnp.dtype(opts.get("dtype", "bfloat16")),
        remat=bool(opts.get("remat", True)),
        use_flash=bool(opts.get("use_flash", True)),
        rope_theta=dims["rope_theta"])


def prng_key(seed: int):
    """A key for any whole seed from 0, beyond 32 bits too."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def temp_bytes(compiled) -> int:
    """What a compiled program needs on a device beside its arguments and
    results while it runs."""
    analysis = compiled.memory_analysis()
    return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)


def memory_peak(devices, program_temp_bytes: int = 0) -> int:
    """The peak on the fullest device: the allocator's peak of live arrays
    (``peak_bytes_in_use``), which on this runtime leaves out what a
    program holds only while it runs (PR 23 found it shows the train state
    and not the step's temporaries), plus the temporaries of the largest
    program of the window, from the compiler's own ``memory_analysis()``
    of the program that ran."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_, default=0)) + int(program_temp_bytes)


@contextlib.contextmanager
def profiled(env: Env):
    """Trace what runs inside, when the run is a traced one."""
    if not env.trace:
        yield
        return
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # our own spans, not every frame
    jax.profiler.start_trace(env.trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = manifest_mod.ROOT, require_tpu: bool = True,
             t_process_start: Optional[float] = None) -> Dict[str, Any]:
    """Run one cell and return its result line as a dict. ``require_tpu``
    is False only in the CPU tests, which drive the same code at tiny
    sizes; the command line cannot reach it."""
    if t_process_start is None:
        t_process_start = time.monotonic()
    cell = manifest_mod.Manifest(root).cell(name)
    # a cell whose sizes were not written down fails before it takes a chip
    manifest_mod.model_dims(cell.config, cell.job, cell.chips)
    device = device_info()
    if require_tpu:
        if device["platform"] != "tpu":
            raise NoDevice(f"JAX found {device}, not a TPU")
        if device["count"] < cell.chips:
            raise NoDevice(f"cell {name} asks for {cell.chips} chip(s), "
                           f"JAX found {device['count']}")
        peaks.peak(device["kind"])      # UnknownDevice: no silent default
    env = Env(cell=cell, seed=seed, seconds=seconds, trace=trace,
              on_tpu=require_tpu)
    if trace:
        env.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        if cell.job == "train":
            from benchmark import train_job as job
        elif cell.job == "serve":
            from benchmark import serve_job as job
        else:
            raise manifest_mod.ManifestError(
                f"cell {name}: unknown job {cell.job!r}")
        outcome = job.run(env)
        setup_s = outcome.t_first_measured - t_process_start
        for note in outcome.notes:
            say(note)
        result: Dict[str, Any] = {
            "correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": {},
            "device": {**device,
                       "memory_peak_bytes": outcome.memory_peak_bytes},
        }
        units = {m["name"]: m["unit"]
                 for m in cell.end_to_end + cell.per_layer}
        if not trace:
            values = {**outcome.end_to_end, "setup_s": setup_s}
            wanted = [m["name"] for m in cell.end_to_end]
        else:
            from benchmark import reducers, trace_reduce
            reduced = trace_reduce.load(
                trace_reduce.find_xplane(env.trace_dir), SPAN_PREFIX,
                WINDOW_SPAN)
            ctx = reducers.Context(cell=cell, trace=reduced,
                                   counters=outcome.counters,
                                   device_kind=device["kind"])
            values = reducers.evaluate(cell.per_layer, ctx)
            wanted = [m["name"] for m in cell.per_layer]
            result["device"]["busy_s"] = reduced.busy_s
            result["device"]["window_s"] = reduced.window_s
            result["breakdown"] = reduced.breakdown()
            if not reduced.busy_s > 0:
                result["correct"] = False
                say("no operation ran on the device inside the window")
        for metric in wanted:
            v = values.get(metric)
            if v is None or not math.isfinite(v):
                # a per-layer reader that found nothing is left out; an
                # end-to-end metric has to be there
                result["correct"] = result["correct"] and trace
                say(f"metric {metric} has no value")
                continue
            result["metrics"][metric] = {"value": float(v),
                                         "unit": units[metric]}
        return result
    finally:
        if env.trace_dir:
            shutil.rmtree(env.trace_dir, ignore_errors=True)
        _shutdown_runtime()


def _shutdown_runtime() -> None:
    import ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def main(name: str, seed: int, seconds: float, trace: bool, *,
         t_process_start: float) -> int:
    try:
        result = run_cell(name, seed, seconds, trace,
                          t_process_start=t_process_start)
    except (NoDevice, peaks.UnknownDevice,
            manifest_mod.ManifestError) as e:
        print(f"[bench] cannot measure: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_DEVICE
    except Exception:  # noqa: BLE001 - a run that fails prints no result
        traceback.print_exc()
        return EXIT_NO_DEVICE
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return EXIT_OK if result["correct"] else EXIT_INCORRECT
