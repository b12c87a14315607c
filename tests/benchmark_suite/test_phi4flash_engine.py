"""A tiny copy of Phi-4-mini-flash-reasoning's cell (``phi4flash-serve-reason``)
through ``serve.run`` and the generation engine, and the comparison that
decides ``correct`` on the job's own slot model: at toy sizes on the CPU (the
cell's files, reference and counts are ``test_phi4flash_cell.py``'s)."""

import json
import os

import jax
import numpy as np
import pytest

import benchmark_tiny
from benchmark import generate_job, harness, manifest, traffic
from benchmark.adapters import phi4flash_decoder
from test_phi4flash_cell import CELL, SEED, TINY_DIMS, TINY_PHI


@pytest.fixture
def runtime():
    import ray_tpu
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


# -- a tiny copy of the cell, through serve.run and the engine ------------------------------


@pytest.fixture(scope="module")
def phi_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file that
    names the adapter, a closed loop of three callers with answers' lengths,
    a deployment of three slots and three buckets; new files and entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("phi4flash"),
                                    cells=("tiny-serve-closed",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-phi4flash.json"),
              "w") as f:
        json.dump(TINY_PHI, f)
    with open(os.path.join(base, "traffic", "tiny-reason.json"), "w") as f:
        json.dump({"name": "tiny-reason", "kind": "requests",
                   "loop": "closed", "pattern_seed": 1,
                   "answer_pattern_seed": 2, "clients": 3, "n_lengths": 12,
                   "arrange": "by_client", "preroll_s": 0.5,
                   "timeout_s": 30.0,
                   "prompt_len": {"dist": "lognormal", "median": 10,
                                  "sigma": 0.6, "min": 3, "max": 32},
                   "answer_len": {"dist": "lognormal", "median": 8,
                                  "sigma": 0.5, "min": 2, "max": 14}}, f)
    with open(os.path.join(base, "workloads", "tiny-phi4flash-reason.json"),
              "w") as f:
        json.dump({"name": "tiny-phi4flash-reason", "job": "generate",
                   "chips": 1,
                   "deployment": {"slots": 3, "cache_len": 48,
                                  "length_buckets": [8, 16, 32],
                                  "route": "/generate"},
                   "model": {"dtype": "float32", "use_flash": False},
                   "reference": {"prompt_lengths": [5, 12, 32],
                                 "max_new_tokens": 14}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-phi4flash", "source": "tests only",
        "file": "benchmark/configs/tiny-phi4flash.json", "reduced": [],
        "why": "a toy of the stack"})
    data["workloads"].append({
        "name": "tiny-phi4flash-reason", "config": "tiny-phi4flash",
        "traffic": "tiny-reason", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-phi4flash-reason")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_generates_through_the_engine_and_is_correct(
        phi_root, runtime):
    result = harness.run_cell("tiny-phi4flash-reason", SEED, 1.0, False,
                              root=phi_root, require_tpu=False)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_traced_tiny_cell_reads_the_engines_spans(phi_root, runtime):
    """On the CPU there is no device plane: the readers of the device's
    trace return nothing and the line leaves their metrics out; the engine's
    own spans are read."""
    result = harness.run_cell("tiny-phi4flash-reason", SEED, 1.0, True,
                              root=phi_root, require_tpu=False)
    assert set(result["metrics"]) == {"cache_live_pct.phi4flash",
                                      "slots_occupied_mean.granite",
                                      "admit_wait_ms.granite",
                                      "serve_startup_s.serve"}
    assert 1.0 <= result["metrics"]["slots_occupied_mean.granite"]["value"]
    assert 0.0 < result["metrics"]["cache_live_pct.phi4flash"][
        "value"] <= 100.0


@pytest.mark.parametrize("fault", [None, "bfloat16_state"])
def test_the_comparison_passes_the_program_and_fails_a_planted_fault(
        fault, monkeypatch):
    """The comparison that decides ``correct``, on the job's own slot model
    behind an engine (no proxy): the program as built reads float32 rounding;
    one that keeps its recurrent state in bfloat16 reads over a limit between
    the two (both sides are float32 here; the pieces of the mathematics left
    out are ``test_phi4flash_layer.py``'s)."""
    import threading

    from ray_tpu.models import transformer
    from ray_tpu.serve.generation import GenerationEngine
    if fault == "bfloat16_state":
        step, scan = (transformer.selective_scan_step,
                      transformer.selective_scan)

        def rounded(y, state):
            # not a pair of converts: the TPU compiler folds those away
            return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)

        monkeypatch.setattr(transformer, "selective_scan_step",
                            lambda *a: rounded(*step(*a)))
        monkeypatch.setattr(transformer, "selective_scan",
                            lambda *a, **k: rounded(*scan(*a, **k)))
    model = generate_job._generator_class()(
        "direct", TINY_PHI, TINY_DIMS,
        {"dtype": "float32", "use_flash": False},
        {"slots": 3, "cache_len": 48, "length_buckets": [8, 16, 32]}, SEED,
        False)
    engine = GenerationEngine(model, "direct", "direct-engine")
    prompts = [traffic.prompt_tokens(SEED, i, n, 96)
               for i, n in enumerate((5, 12, 32))]
    replies = [None] * 3
    model.watch(prompts, 14)

    def call(i):
        replies[i] = engine.submit({"prompt": prompts[i],
                                    "max_new_tokens": 14})

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    engine.shutdown()
    states = [np.asarray(model.kept[i]) for i in range(3)]
    assert states[0].shape == (3, 4, 64)
    check = generate_job.compare(replies, states, prompts, 14,
                                 phi4flash_decoder, TINY_DIMS, SEED,
                                 jax.devices()[0])
    assert len(check["rows"]) == 3
    assert (check["worst"] > 3e-6) == (fault is not None), check
    assert (check["state_worst"] > 1e-5) == (fault is not None), check
