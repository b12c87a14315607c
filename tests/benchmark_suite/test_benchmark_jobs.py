"""Each job end to end at a toy size on the CPU, through the same
``JaxTrainer.fit`` / ``serve.run`` calls and the same harness as on the
chip, by the harness's test-only entry (``require_tpu=False``); and the
measuring entry's refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

import benchmark_tiny
from benchmark import harness, manifest, serve_job, train_job

SEED = 2**31 + 77      # beyond 32 signed bits, as the driver's are
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchmark_tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def runtime():
    import ray_tpu
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def _run(root, cell, trace=False, seconds=2.0):
    return harness.run_cell(cell, SEED, seconds, trace, root=root,
                            require_tpu=False)


def _well_formed(result, cell, trace, root):
    assert set(result) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert set(result["device"]) == DEVICE_KEYS | (
        {"busy_s", "window_s"} if trace else set())
    json.dumps(result)          # every value is plain JSON
    c = manifest.Manifest(root).cell(cell)
    units = {m["name"]: m["unit"] for m in c.end_to_end + c.per_layer}
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == units[name]
        assert isinstance(metric["value"], float)


def test_train_cell_runs_through_fit_and_reports_its_end_to_end_metrics(
        root, runtime):
    result = _run(root, "tiny-train")
    _well_formed(result, "tiny-train", False, root)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    steps = result["attempted"]
    assert steps >= 3
    # whole steps of 2 x 32 tokens over the time they took
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def test_sharded_train_cell_builds_its_own_mesh_over_four_devices(
        root, runtime):
    result = _run(root, "tiny-train-fsdp4")
    _well_formed(result, "tiny-train-fsdp4", False, root)
    assert result["correct"], result
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    traced = _run(root, "tiny-train-fsdp4", trace=True, seconds=1.0)
    # collective_exposed_ms.train4 reads the trace: nothing to read here
    assert "collective_exposed_ms.train4" not in traced["metrics"]


def test_traced_train_cell_reports_per_layer_metrics_and_a_breakdown(
        root, runtime):
    result = _run(root, "tiny-train", trace=True)
    _well_formed(result, "tiny-train", True, root)
    # the CPU has no device plane: the readers of the trace find nothing
    # and are left out, and a run with no device operation is not correct
    assert set(result["metrics"]) == {"fit_startup_s.train",
                                      "data_wait_ms.train"}
    assert result["device"]["busy_s"] == 0.0 and not result["correct"]
    assert result["device"]["window_s"] == pytest.approx(2.0, abs=0.5)
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    spans = {name for name, _ in result["breakdown"]["idle_gaps"]}
    assert {"step_fn", "next(batch)", "session.report"} <= spans


def test_steady_serving_cell_goes_over_http_from_a_child(root, runtime):
    result = _run(root, "tiny-serve-open")
    _well_formed(result, "tiny-serve-open", False, root)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p95_ms", "setup_s"}
    assert result["attempted"] == 40      # 20 req/s for 2 s, every seed
    traced = _run(root, "tiny-serve-open", trace=True)
    _well_formed(traced, "tiny-serve-open", True, root)
    # the median stands beside the tail as a per-layer metric
    assert 0 < traced["metrics"]["ttft_p50_ms.steady"]["value"]
    assert traced["metrics"]["queue_wait_ms.steady"]["value"] >= 0


def test_offline_serving_cell_counts_tokens_and_batches(root, runtime):
    result = _run(root, "tiny-serve-closed")
    assert result["correct"]
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = _run(root, "tiny-serve-closed", trace=True)
    _well_formed(traced, "tiny-serve-closed", True, root)
    batch = traced["metrics"]["batch_size_mean.offline"]["value"]
    assert 1.0 <= batch <= 4.0
    assert traced["metrics"]["serve_startup_s.serve"]["value"] > 0


def test_a_wrong_output_makes_the_run_incorrect(root, runtime, monkeypatch):
    monkeypatch.setattr(serve_job, "LOGIT_ATOL", 0.0)
    assert not _run(root, "tiny-serve-open", seconds=1.0)["correct"]
    monkeypatch.setattr(train_job, "LOSS_RTOL", 0.0)
    assert not _run(root, "tiny-train", seconds=1.0)["correct"]


def test_a_compilation_inside_the_window_makes_the_run_incorrect(
        root, runtime, monkeypatch):
    monkeypatch.setattr(train_job, "WARMUP_STEPS", 0)
    assert not _run(root, "tiny-train", seconds=1.0)["correct"]


def _measure(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=benchmark_tiny.REPO)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_the_measuring_entry_fails_without_a_tpu_and_prints_no_result():
    proc = _measure(["--workload", "mistral7b-train-4k", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], benchmark_tiny.REPO)
    assert proc.returncode == 2
    assert "not a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_the_measuring_entry_refuses_an_unknown_cell_and_a_bad_seed():
    proc = _measure(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1"], benchmark_tiny.REPO)
    assert proc.returncode == 2 and not proc.stdout.strip()
    proc = _measure(["--workload", "mistral7b-train-4k", "--seed", "-1",
                     "--seconds", "1"], benchmark_tiny.REPO)
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_the_compile_cache_is_inside_the_checkout_unless_the_environment_names_one():
    from benchmark import run
    env = {}
    assert run.set_compile_cache(env) == os.path.join(benchmark_tiny.REPO,
                                                      ".jax_cache")
    assert run.set_compile_cache({"JAX_COMPILATION_CACHE_DIR": "/x"}) == "/x"
