"""The looped decoder's configuration (Ouro-2.6B), adapter, counts, readers
and cell: the manifest with five cells, the published row, a tiny copy of
the cell through ``JaxTrainer.fit`` on the CPU (correct, and incorrect with
a wrong reference), the serving job's program against the reference's last
logits, the counts against a hand count and the three readers on synthetic
planes."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
from benchmark import (harness, looped_counts, looped_reference, manifest,
                       program_spans, reducers, serve_job)
from benchmark.adapters import looped_decoder
from benchmark.trace_reduce import DeviceTrace, Event, Reduced
from test_benchmark_manifest import (CELLS, ROOTS, cell_order_faults,
                                     real_root)

REPO = benchmark_tiny.REPO
SEED = 2**31 + 30
CELL = "ouro2.6b-train-4k"
# the catalog row's config (architectures.jsonl, Ouro-2.6B), by hand
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 5632, "head_dim": 128,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "vocab_size": 49152, "num_hidden_layers": 48, "total_ut_steps": 4,
    "early_exit_threshold": 1, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "hidden_act": "silu", "model_type": "ouro", "rope_scaling": None,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "layer_types": ["full_attention"] * 48,
}
TINY_LOOPED = {
    "name": "tiny-looped", "source": "tests only",
    "adapter": "benchmark.adapters.looped_decoder",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 128,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0, "total_ut_steps": 3,
    "early_exit_threshold": 1, "assumed": {"exit_beta": 0.05},
    "reduced": {"train.1": {"num_hidden_layers": 2, "why": "tests"},
                "serve.1": {"why": "nothing cut"}},
}
TINY_DIMS = {
    "vocab_size": 128, "d_model": 64, "n_layers": 2, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "d_ff": 96, "rope_theta": 1e6,
    "rms_norm_eps": 1e-6, "total_ut_steps": 3, "early_exit_threshold": 1.0,
    "exit_beta": 0.05}
US = 1000


@pytest.fixture(scope="module", params=ROOTS)
def real(request, tmp_path_factory):
    return manifest.Manifest(real_root(request.param, tmp_path_factory))


@pytest.fixture
def runtime():
    import ray_tpu
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


# -- the manifest with the fifth cell ----------------------------------------


def test_the_manifest_has_five_cells_and_every_guard_holds(real):
    assert manifest.check(real) == []
    assert cell_order_faults(real.data["workloads"]) == []
    # the accepted four and the looped cell come first and in order; what
    # a later PR adds follows them
    assert real.cell_names()[:5] == CELLS + [CELL]
    entry, = [w for w in real.data["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ouro-2.6b", "packed-4k-1seq", 1)


def test_the_looped_cell_reports_the_training_metrics_and_its_own(real):
    cell = real.cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == [
        "fit_startup_s.train", "data_wait_ms.train", "host_gap_ms.train",
        "step_device_ms.train", "flash_share_pct.train",
        "flash_fwd_ms.train", "flash_dq_ms.train", "flash_dkv_ms.train",
        "flash_roofline_pct.looped", "flash_calls_per_step.looped",
        "exit_head_share_pct.looped"]
    # the dense decoder's count would read a quarter of the truth here
    assert "flash_roofline_pct.train" not in names
    for m in cell.per_layer[8:]:
        assert m["workloads"] == [CELL]
        assert callable(reducers.resolve(m["reducer"]))
    assert [m["reducer"] for m in cell.per_layer[8:]] == [
        "benchmark.looped_counts:flash_roofline_pct",
        "benchmark.looped_counts:flash_fwd_calls_per_step",
        "benchmark.looped_counts:exit_head_share_pct"]
    # the kernel's three times by the accepted reader: since PR 60 the
    # ``.train`` entries themselves (the ``.looped`` ones named the same
    # reader and folded into them), with this cell in their lists
    for m, kind in zip(cell.per_layer[5:8], ("fwd", "dq", "dkv")):
        assert reducers.resolve(m["reducer"]) is program_spans.kernel_ms
        assert m["params"] == {"kernel": f"flash_{kind}"}
        assert m["workloads"][:3] == [
            "mistral7b-train-4k", "mistral7b-train-4k-fsdp4", CELL]
    assert cell.traffic["seq_len"] == 4096
    assert cell.deploy["model"] == {"dtype": "bfloat16", "remat": True,
                                    "use_flash": True}


def test_the_configuration_keeps_every_published_key(real):
    entry = next(c for c in real.data["configs"] if c["name"] == "ouro-2.6b")
    with open(os.path.join(real.root, entry["file"])) as f:
        config = json.load(f)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert list(config["reduced"]) == ["train.1"]
    cut = config["reduced"]["train.1"]
    assert set(cut) == {"num_hidden_layers", "why", "stands_for"}
    assert 4 <= cut["num_hidden_layers"] <= 9     # ISSUE 30's rule
    assert config["assumed"]["exit_beta"] == 0.05
    assert config["departures"]


def test_looped_dims_are_the_published_sizes_with_the_cells_cut(real):
    cell = real.cell(CELL)
    dims = manifest.model_dims(cell.config, "train", 1)
    assert manifest.adapter(cell.config) is looped_decoder
    layers = cell.config["reduced"]["train.1"]["num_hidden_layers"]
    assert dims == {
        "vocab_size": 49152, "d_model": 2048, "n_layers": layers,
        "n_heads": 16, "n_kv_heads": 16, "head_dim": 128, "d_ff": 5632,
        "rope_theta": 1e6, "rms_norm_eps": 1e-6, "total_ut_steps": 4,
        "early_exit_threshold": 1.0, "exit_beta": 0.05}
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "serve", 1)


def test_looped_program_config_hands_the_program_the_loop():
    from ray_tpu.models.transformer import TransformerConfig
    cfg = looped_decoder.program_config(TINY_DIMS, 32, {"dtype": "float32",
                                                        "use_flash": False})
    assert cfg == TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=96, max_seq_len=32, dtype=jnp.dtype("float32"), remat=True,
        use_flash=False, rope_theta=1e6, norm_eps=1e-6, n_passes=3,
        post_norm=True, exit_beta=0.05)
    with pytest.raises(ValueError, match="no adaptive exit"):
        looped_decoder.program_config(
            {**TINY_DIMS, "early_exit_threshold": 0.5}, 32, {})


# -- a tiny copy of the cell, through JaxTrainer.fit ---------------------------


@pytest.fixture(scope="module")
def looped_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file
    that names the looped adapter, a workload file, entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("looped"),
                                    cells=("tiny-train", "tiny-serve-open"))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-looped.json"), "w") as f:
        json.dump(TINY_LOOPED, f)
    cells = {"tiny-looped-train": ("tiny-batches", "tiny-train", CELL),
             "tiny-looped-serve": ("tiny-open", "tiny-serve-open",
                                   "internlm2-serve-steady")}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-looped", "source": "tests only",
        "file": "benchmark/configs/tiny-looped.json",
        "reduced": ["num_hidden_layers"], "why": "a toy looped decoder"})
    for name, (traffic, like, reports_like) in cells.items():
        deploy = dict(benchmark_tiny.TINY_CELLS[like], name=name)
        del deploy["traffic"], deploy["like"]
        with open(os.path.join(base, "workloads", name + ".json"), "w") as f:
            json.dump(deploy, f)
        data["workloads"].append({
            "name": name, "config": "tiny-looped", "traffic": traffic,
            "chips": 1, "why": "a toy of the looped cell"})
        for metric in data["end_to_end"] + data["per_layer"]:
            if reports_like in metric.get("workloads", ()):
                metric["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_looped_cell_trains_through_fit_and_is_correct(
        looped_root, runtime):
    result = harness.run_cell("tiny-looped-train", SEED, 1.0, False,
                              root=looped_root, require_tpu=False)
    assert result["correct"] and result["failed"] == 0, result
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] >= 1


def test_a_traced_tiny_looped_cell_leaves_out_what_it_cannot_read(
        looped_root, runtime):
    result = harness.run_cell("tiny-looped-train", SEED, 1.0, True,
                              root=looped_root, require_tpu=False)
    # no device plane on the CPU: the three looped readers return nothing
    assert set(result["metrics"]) == {"fit_startup_s.train",
                                      "data_wait_ms.train"}


@pytest.mark.parametrize("wrong", ["one_pass_fewer", "no_entropy_term"])
def test_a_wrong_looped_reference_makes_the_cell_incorrect(
        looped_root, runtime, monkeypatch, wrong):
    sound = looped_decoder.loss_and_grad_norm

    def reference(params, tokens, dims):
        if wrong == "one_pass_fewer":
            dims = {**dims, "total_ut_steps": dims["total_ut_steps"] - 1}
        else:
            dims = {**dims, "exit_beta": 0.0}
        return sound(params, tokens, dims)

    monkeypatch.setattr(looped_decoder, "loss_and_grad_norm", reference)
    result = harness.run_cell("tiny-looped-train", SEED, 1.0, False,
                              root=looped_root, require_tpu=False)
    assert not result["correct"] and result["failed"] == 0


def test_the_serving_jobs_program_replies_with_the_last_passes_logits():
    """``serve_job.LastToken.first_token`` (``backbone`` + ``head``, no
    edit for the looped model) against the reference's ``last_logits``."""
    replica = serve_job.LastToken(
        "looped-serve-test", TINY_LOOPED, {**TINY_DIMS, "n_layers": 3},
        {"dtype": "float32", "use_flash": False}, batch_buckets=[2],
        length_buckets=[16], seed=SEED, on_tpu=False)
    try:
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(0, 128, n)) for n in (16, 9)]
        replies = replica(prompts)
        for prompt, reply in zip(prompts, replies):
            want = looped_reference.last_logits(
                replica.params, jnp.asarray([prompt], jnp.int32),
                {**TINY_DIMS, "n_layers": 3})[0]
            assert reply["token"] == int(jnp.argmax(want))
            assert abs(reply["logit"] - float(jnp.max(want))) < 1e-4
            # one pass fewer is another reply, by the adapter's own limit
            fewer = looped_reference.last_logits(
                replica.params, jnp.asarray([prompt], jnp.int32),
                {**TINY_DIMS, "n_layers": 3, "total_ut_steps": 2})[0]
            assert float(jnp.max(jnp.abs(want - fewer))) > (
                looped_decoder.TOLERANCES["logit_atol"])
    finally:
        serve_job._LIVE.pop("looped-serve-test", None)


def test_a_tiny_looped_cell_serves_and_is_correct(looped_root, runtime):
    result = harness.run_cell("tiny-looped-serve", SEED, 1.0, False,
                              root=looped_root, require_tpu=False)
    assert result["correct"] and result["failed"] == 0, result
    assert set(result["metrics"]) == {"ttft_p95_ms", "setup_s"}


# -- the counts, against a hand count -----------------------------------------

OURO7 = {"vocab_size": 49152, "d_model": 2048, "n_layers": 7, "n_heads": 16,
         "n_kv_heads": 16, "head_dim": 128, "d_ff": 5632,
         "total_ut_steps": 4}


def test_flops_a_token_are_the_issues_formula():
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    for layers, gflop in ((7, 12.45), (6, 11.02)):
        dims = {**OURO7, "n_layers": layers}
        by_hand = 3 * 4 * (layers * (2 * layer + 2 * 16 * 4096 * 128)
                           + 2 * 2048 * 49152)
        assert looped_counts.train_flops_per_token(dims, 4096) == by_hand
        assert by_hand / 1e9 == pytest.approx(gflop, abs=0.01)
    # four heads of 2 x 2048 x 49,152 a token: 19% at 7 layers, 4% whole
    assert looped_counts.head_flops_share(OURO7, 4096) == pytest.approx(
        0.194, abs=1e-3)
    assert looped_counts.head_flops_share({**OURO7, "n_layers": 48}, 4096
                                          ) == pytest.approx(0.034, abs=1e-3)
    # one pass is the dense decoder's count
    from benchmark import flops
    one = {**OURO7, "total_ut_steps": 1}
    assert looped_counts.train_flops_per_token(one, 4096) == (
        flops.train_flops_per_token(one, 4096))


def test_flash_calls_a_step_count_every_layer_of_every_pass():
    assert looped_counts.flash_calls_per_step(OURO7, True) == {
        "fwd": 56, "dq": 28, "dkv": 28}
    assert looped_counts.flash_calls_per_step(OURO7, False) == {
        "fwd": 28, "dq": 28, "dkv": 28}
    least = looped_counts.flash_min_seconds_per_step(OURO7, 1, 4096, True,
                                                     "TPU v5 lite")
    # one causal seq x seq x head_dim product over 16 heads: 34.4 GFLOP;
    # fwd 2, dq 3, dk/dv 4 of them, all bound by FLOPs at 4,096 tokens
    product = 2 * 16 * 4096 * 4096 * 128 / 2
    by_hand = (56 * 2 + 28 * 3 + 28 * 4) * product / 197e12
    assert least["seconds"] == pytest.approx(by_hand, rel=1e-12)
    assert set(least["bounds"].values()) == {"flops"}


# -- the readers, on synthetic planes ------------------------------------------


def ev(name, start_us, end_us, category=""):
    return Event(name, start_us * US, end_us * US, category)


KERNEL = "custom-call tpu_custom_call"


def _context(ops, modules, dims=OURO7, layers=1, passes=2):
    dims = {**dims, "n_layers": layers, "total_ut_steps": passes}
    cell = manifest.Cell(name="c", chips=1, config_name="x", config={},
                         traffic={}, deploy={"job": "train",
                                             "model": {"remat": True}},
                         end_to_end=[], per_layer=[], root=REPO)
    window = (0, max(m.end for m in modules) + US)
    return reducers.Context(
        cell=cell, trace=Reduced(window, {0: DeviceTrace(ops, modules)}, []),
        counters={"dims": dims, "sequences_per_step": 1, "devices": 1,
                  "seq_len": 4096}, device_kind="TPU v5 lite")


def _one_step(at):
    """One layer, two passes, under remat: four forwards, two dq, two
    dk/dv; a ``while`` spans them."""
    names = ["flash_fwd", "flash_fwd.1", "flash_fwd.2", "flash_fwd.3",
             "flash_dq", "flash_dq.1", "flash_dkv", "flash_dkv.1"]
    ops = [ev("while.7", at, at + 900)]
    for i, name in enumerate(names):
        ops.append(ev(name, at + 100 * i, at + 100 * i + 50, KERNEL))
    ops.append(ev("fusion.9", at + 800, at + 900, "fusion"))
    return ops


def test_the_flash_call_count_reads_forwards_inside_one_step():
    ops = _one_step(0) + _one_step(1000)
    modules = [ev("jit_step(1)", 0, 900), ev("jit_step(1)", 1000, 1900)]
    ctx = _context(ops, modules)
    assert looped_counts.flash_fwd_calls_per_step(
        ctx, {"program": "jit_step"}) == 4.0
    assert "= 4)" in ctx.notes[-1]
    # another architecture's cell, a trace without the program, no trace
    plain = _context(ops, modules)
    del plain.counters["dims"]["total_ut_steps"]
    assert looped_counts.flash_fwd_calls_per_step(
        plain, {"program": "jit_step"}) is None
    assert looped_counts.flash_fwd_calls_per_step(
        ctx, {"program": "jit_other"}) is None
    ctx.trace = None
    assert looped_counts.flash_fwd_calls_per_step(
        ctx, {"program": "jit_step"}) is None


def test_the_looped_roofline_counts_every_pass():
    ops = _one_step(0)
    ctx = _context(ops, [ev("jit_step(1)", 0, 900)])
    got = looped_counts.flash_roofline_pct(
        ctx, {"program": "jit_step", "ops": ["tpu_custom_call"]})
    least = looped_counts.flash_min_seconds_per_step(
        ctx.counters["dims"], 1, 4096, True, "TPU v5 lite")["seconds"]
    assert got == pytest.approx(100 * least / (8 * 50e-6))
    # twice the dense decoder's reading of the same trace, for two passes
    dense = reducers.flash_roofline_pct(
        ctx, {"program": "jit_step", "ops": ["tpu_custom_call"]})
    assert got == pytest.approx(2 * dense)
    assert looped_counts.flash_roofline_pct(
        _context([ev("fusion.1", 0, 10, "fusion")],
                 [ev("jit_step(1)", 0, 900)]),
        {"program": "jit_step", "ops": ["tpu_custom_call"]}) is None


def test_a_shape_holds_the_vocabulary_only_as_a_whole_dimension():
    text = ("%fusion.259 = f32[1,4096,49152]{2,1,0:T(8,128)} fusion("
            "bf16[1,4096,2048]{2,1,0} %p, bf16[2048,49152]{1,0} %w), "
            "kind=kOutput, calls=%fused_computation.1")
    assert looped_counts.holds_size(text, 49152)
    assert looped_counts.holds_size(text, 2048)
    assert not looped_counts.holds_size(text, 4915)
    assert not looped_counts.holds_size(text, 259)      # a name, no shape
    assert not looped_counts.holds_size(
        "%copy.1 = bf16[149152,8]{1,0} copy(bf16[149152,8]{1,0} %x)", 49152)


def test_the_exit_head_share_is_by_shape_inside_one_step():
    def op(name, shape, start, end):
        return ev(f"%{name} = {shape} fusion({shape} %p), kind=kLoop",
                  start, end)

    ops = [op("while.1", "(f32[1,64,128], s32[])", 0, 100),
           op("fusion.1", "f32[1,64,128]{2,1,0}", 0, 30),    # head
           op("fusion.2", "bf16[1,64,32]{2,1,0}", 30, 100),  # a block
           op("fusion.3", "f32[128,32]{1,0}", 100, 120),     # embedding
           op("fusion.4", "f32[1,64,128]{2,1,0}", 500, 600)]  # outside
    steps = [ev("jit_step(1)", 0, 200)]
    notes = []
    share = looped_counts.vocab_ops_share_pct(steps, ops, 128, notes)
    assert share == pytest.approx(100 * (30 + 20) / 120)
    assert "fusion.1 0.000030" in notes[0] and "fusion.3" in notes[0]
    assert "fusion.4" not in notes[0] and "while.1" not in notes[0]
    assert looped_counts.vocab_ops_share_pct(steps, ops, 4096) is None
    assert looped_counts.vocab_ops_share_pct([], ops, 128) is None


def test_the_exit_head_reader_returns_nothing_without_the_runs_file():
    ctx = _context(_one_step(0), [ev("jit_step(1)", 0, 900)])
    assert looped_counts.exit_head_share_pct(
        ctx, {"program": "jit_step"}) is None
