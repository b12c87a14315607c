"""Granite 4.0-H's cell (``granite4h-serve-chat``): its files, its adapter
and streamed reference, its counts against the issue's hand counts, the
traffic's cycle, and a tiny copy of the cell through ``serve.run`` and the
generation engine: at toy sizes on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
from benchmark import (generate_job, granite_counts, granite_reference,
                       harness, manifest, reducers, traffic)
from benchmark.adapters import granite_decoder
from test_benchmark_manifest import ROOTS, real_root

CELL = "granite4h-serve-chat"
CONFIG = "granite-4.0-h-micro"
TRAFFIC = "chat192x160-closed64"
SEED = 2**31 + 52
PERIOD = 5 * ["mamba"] + ["attention"] + 4 * ["mamba"]
# the catalog row's ``config``, key for key
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": 4 * PERIOD,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
# d 32, 4 query heads over 2 K/V heads of 8, 4 Mamba heads of 16 against a
# state of 8, chunks of 8, a stack of two Mamba layers, attention, Mamba
TINY_GRANITE = {
    **PUBLISHED, "name": "tiny-granite", "source": "tests only",
    "adapter": "benchmark.adapters.granite_decoder",
    "hidden_size": 32, "intermediate_size": 48,
    "shared_intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 8, "mamba_chunk_size": 8, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "attention_multiplier": 0.1, "vocab_size": 96,
    "reduced": {"generate.1": {"why": "tests"}},
}
TINY_DIMS = granite_decoder.dims(TINY_GRANITE, "generate", 1)
METRICS = {
    "decode_step_device_ms.granite": ("device_trace", "Model"),
    "prefill_device_ms.granite": ("device_trace", "Model"),
    "decode_share_pct.granite": ("device_trace", "Model"),
    "window_mfu_pct.granite": ("device_trace", "Model"),
    "decode_hbm_roofline_pct.granite": ("device_trace", "Model"),
    "ssm_step_roofline_pct.granite": ("device_trace", "Kernel"),
    "ssd_prefill_roofline_pct.granite": ("device_trace", "Kernel"),
    "mamba_share_pct.granite": ("device_trace", "Model"),
    "attn_share_pct.lfm2": ("device_trace", "Model"),
    "mlp_share_pct.lfm2": ("device_trace", "Model"),
    "head_share_pct.granite": ("device_trace", "Model"),
    "unscoped_share_pct.lfm2": ("device_trace", "Model"),
    "slots_occupied_mean.granite": ("program_span",
                                    "Serve: generation engine"),
    "admit_wait_ms.granite": ("program_span", "Serve: generation engine"),
    "step_host_gap_ms.granite": ("device_trace", "Serve: generation engine"),
    "serve_startup_s.serve": ("host_clock", "Entry: serve API"),
}
# PR 60 folded the entries that repeat another's reader into the first of
# their kind (the three shares into LFM2's, the start-up into the serving
# cells' own) and retired the constructor's gauge: the cell reports sixteen
SETUP = ("serve_startup_s.serve",)
OWN = [name for name in METRICS if name.endswith(".granite")]
# of its own, those the SmallThinker cell reports too
SHARED = ("decode_step_device_ms.granite", "prefill_device_ms.granite",
          "admit_wait_ms.granite")


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


# -- the files ---------------------------------------------------------------------------


def test_the_manifest_is_clean_and_holds_the_cell_at_its_end(real):
    assert manifest.check(real) == []
    # the tenth cell, after the accepted nine, of which one takes four chips
    names = real.cell_names()
    assert names.index(CELL) == 9 == 1 + names.index("kanana2-train-8k")
    assert [w["name"] for w in real.data["workloads"][:10]
            if w["chips"] == 4] == ["mistral7b-train-4k-fsdp4"]
    entry = real.data["configs"][7]
    assert entry["name"] == CONFIG and entry["reduced"] == []
    throughput = next(m for m in real.data["end_to_end"]
                      if m["name"] == "serve_tokens_per_s")
    assert CELL in throughput["workloads"] and throughput["bound"] == 0.06


def test_the_cell_reports_throughput_set_up_and_its_sixteen_metrics(real):
    cell = real.cell(CELL)
    assert cell.job == "generate" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"]: (m["source"], m["layer"])
            for m in cell.per_layer} == METRICS
    for m in cell.per_layer:
        assert CELL in m["workloads"]
        assert (m["workloads"] == [CELL]) == (
            m["name"] in OWN and m["name"] not in SHARED)
        assert m["moves"] == ("setup_s" if m["name"] in SETUP
                              else "serve_tokens_per_s")
        assert callable(reducers.resolve(m["reducer"]))
    # the cell's own entries stand together, in this order
    names = [m["name"] for m in real.data["per_layer"]]
    first = names.index("decode_step_device_ms.granite")
    assert names[first:first + len(OWN)] == OWN


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_metrics_file_agrees_with_its_entry(real, name):
    entry = next(m for m in real.data["per_layer"] if m["name"] == name)
    with open(os.path.join(real.dir, "layer_metrics", name + ".json")) as f:
        held = json.load(f)
    # a later cell like one of an entry's cells is appended to its list
    assert {k: held[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"][:len(held["workloads"])] == held["workloads"]
    assert held["what"] and "reducer" in held
    if "roofline" in name or "mfu" in name or "share" in name:
        assert held["unit"] == "%"


def test_the_configuration_keeps_every_published_key_and_cuts_nothing(real):
    config = real.cell(CELL).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["adapter"] == "benchmark.adapters.granite_decoder"
    cut = config["reduced"]["generate.1"]
    assert set(cut) == {"why", "slots_rule", "slots_read"}
    assert len(config["assumed"]) >= 10 and len(config["departures"]) == 4
    dims = granite_decoder.dims(config, "generate", 1)
    assert dims["n_layers"] == 40 and dims["vocab_size"] == 100352
    assert dims["layer_types"].count("attention") == 4
    assert [i for i, t in enumerate(dims["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert (dims["attn_scale"], dims["embed_scale"], dims["residual_scale"],
            dims["logit_scale"]) == (1 / 64, 12.0, 0.22, 1 / 8)
    with pytest.raises(manifest.ManifestError, match="no 'reduced' entry"):
        granite_decoder.dims(config, "serve", 1)


@pytest.mark.parametrize("change, says", [
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"num_local_experts": 62}, "num_local_experts"),
    ({"mamba_expand": 4}, "mamba_expand x hidden_size"),
])
def test_a_layer_the_program_does_not_have_is_refused_by_name(change, says):
    with pytest.raises(manifest.ManifestError, match=says):
        granite_decoder.dims({**TINY_GRANITE, **change}, "generate", 1)


def test_a_program_without_the_decode_loop_is_refused_before_a_chip(
        monkeypatch):
    from ray_tpu.models import transformer
    monkeypatch.delattr(transformer, "decode_step")
    with pytest.raises(manifest.ManifestError, match="no decode loop"):
        granite_decoder.dims(TINY_GRANITE, "generate", 1)


def test_the_traffic_and_the_deployment_are_the_issues(real):
    cell = real.cell(CELL)
    mix, opts = cell.traffic, cell.deploy["deployment"]
    assert (mix["loop"], mix["clients"], mix["preroll_s"],
            mix["timeout_s"]) == ("closed", 64, 8.0, 60.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.5, "min": 32, "max": 384}
    assert (opts["slots"], opts["cache_len"], opts["length_buckets"]) == (
        64, 1408, [128, 256, 512, 1024])
    assert opts["cache_len"] == mix["prompt_len"]["max"] \
        + mix["answer_len"]["max"]
    # the compared answers are as long as the traffic's longest
    assert cell.deploy["reference"] == {
        "prompt_lengths": [64, 384, 1024],
        "max_new_tokens": mix["answer_len"]["max"]}
    assert set(mix) == {"name", "kind", "loop", "pattern_seed",
                        "answer_pattern_seed", "clients", "n_lengths",
                        "preroll_s", "prompt_len", "answer_len", "timeout_s",
                        "why"}
    # the smallest multiple of the callers that held the 262-277 replies of
    # a window in PR 52 (since PR 53 a window holds 321-339 and counts the
    # first few requests twice)
    assert mix["n_lengths"] == 5 * mix["clients"]


def test_every_seed_sends_the_same_cycle_from_another_place(real):
    mix = real.cell(CELL).traffic
    plans = [generate_job.request_plan(mix, seed)
             for seed in (0, 7, 2**31 + 5)]
    pairs = [sorted(zip(p["lengths"], p["answers"])) for p in plans]
    assert pairs[0] == pairs[1] == pairs[2]
    assert len({tuple(p["lengths"]) for p in plans}) > 1
    first = plans[0]
    assert len(first["lengths"]) == len(first["answers"]) == mix["n_lengths"]
    assert min(first["lengths"]) >= 32 and max(first["lengths"]) <= 1024
    assert min(first["answers"]) >= 32 and max(first["answers"]) <= 384
    assert abs(float(np.median(first["lengths"])) - 192) <= 2
    assert abs(float(np.median(first["answers"])) - 160) <= 2
    # the two arrangements are not one: long prompts do not get long answers
    assert abs(np.corrcoef(first["lengths"], first["answers"])[0, 1]) < 0.2


# -- the reference and the counts ---------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_the_streamed_draw_is_init_params_leaf_for_leaf(seed):
    from ray_tpu.models import transformer
    cfg = granite_decoder.program_config(TINY_DIMS, 64, {"dtype": "float32"})
    key = harness.prng_key(seed)
    ours = jax.jit(lambda k: transformer.init_params(k, cfg))(key)
    theirs = jax.jit(lambda k: granite_reference.draw_tree(k, TINY_DIMS))(key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    jax.tree.map(np.testing.assert_array_equal, ours, theirs)


def test_the_streamed_logits_are_the_whole_trees():
    key = harness.prng_key(SEED)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 20), 0, 96)
    whole = jax.jit(lambda k, t: granite_reference.tree_logits(
        granite_reference.draw_tree(k, TINY_DIMS), t, TINY_DIMS))(key, tokens)
    streamed_fn = jax.jit(lambda k, t, first: granite_reference.logits_from(
        k, t, first, 5, TINY_DIMS))
    streamed = streamed_fn(key, tokens[0], 9)
    np.testing.assert_allclose(streamed, whole[0, 9:14], rtol=1e-4,
                               atol=1e-6)
    last = jax.jit(lambda k, t: granite_reference.last_logits(
        k, t, TINY_DIMS))(key, tokens)
    np.testing.assert_allclose(last, whole[:, -1], rtol=1e-4, atol=1e-6)
    # positions to the right change nothing before them
    padded = jnp.concatenate([tokens[0, :14], jnp.zeros((6,), jnp.int32)])
    np.testing.assert_allclose(streamed_fn(key, padded, 9)[:4],
                               whole[0, 9:13], rtol=1e-4, atol=1e-6)


def test_counts_at_the_published_sizes_are_the_issues(real):
    dims = granite_decoder.dims(real.cell(CELL).config, "generate", 1)
    c = granite_counts
    assert c.layers(dims) == (36, 4)
    # a Mamba layer 17.43 + 8.39 + 50.33 + 0.03 M, an attention layer 60.82 M
    assert c.mamba_params(dims) == 2048 * 8512 + 4096 * 2048
    assert c.attention_params(dims) + c.ffn_params(dims) + 2 * 2048 \
        == pytest.approx(60.82e6, rel=1e-3)
    assert c.param_count(dims) == pytest.approx(3.19e9, rel=2e-3)
    from ray_tpu.models import transformer
    cfg = granite_decoder.program_config(dims, 1408, {})
    shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert c.param_count(dims) == sum(int(np.prod(s.shape))
                                      for s in jax.tree.leaves(shapes))
    # a sequence's state: 75.5 MB of float32, 0.94 MB of tail, 8,192 B of K/V
    # a position
    held = c.state_bytes(1, 1, dims)
    assert held["ssm"] == 36 * 64 * 64 * 128 * 4 == 75_497_472
    assert held["conv"] == 36 * 3 * 4352 * 2 and held["kv"] == 8192
    state = jax.eval_shape(
        lambda: transformer.init_decode_state(cfg, 64, 1408))
    held = c.state_bytes(64, 1408, dims)
    assert sum(held.values()) == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(state)) - 64 * 4
    # a step at 64 slots: 6.38 GB of weights, 9.66 GB of state, 0.74 of K/V
    assert c.decode_step_bytes(64, 1408, dims) == pytest.approx(
        6.38e9 + 9.66e9 + 0.12e9 + 0.74e9, rel=5e-3)
    # the scan: about 4.3 MFLOP a token and layer, 3% of the 152 of the
    # projections and the FFN
    assert c.ssd_fwd_flops(1, 256, dims) / 256 == pytest.approx(4.26e6,
                                                                rel=1e-2)
    assert (2 * (c.mamba_params(dims) + c.ffn_params(dims))
            == pytest.approx(152e6, rel=1e-2))
    assert c.ssd_fwd_flops(1, 300, dims) == c.ssd_fwd_flops(1, 512, dims)
    assert c.ssd_step_bytes(64, dims) == 2 * 64 * 64 * 64 * 128 * 4
    assert c.prefill_flops(1, 1024, dims) > 1024 * c.token_flops(dims)
    assert c.decode_step_flops(64, dims) == pytest.approx(
        64 * 2 * 3.19e9, rel=2e-2)


# -- a tiny copy of the cell, through serve.run and the engine ------------------------------


@pytest.fixture(scope="module")
def granite_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file that
    names the adapter, a closed loop of three callers with answers' lengths,
    a deployment of three slots and three buckets; new files and entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("granite"),
                                    cells=("tiny-serve-closed",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-granite.json"), "w") as f:
        json.dump(TINY_GRANITE, f)
    with open(os.path.join(base, "traffic", "tiny-chat.json"), "w") as f:
        json.dump({"name": "tiny-chat", "kind": "requests", "loop": "closed",
                   "pattern_seed": 1, "answer_pattern_seed": 2, "clients": 3,
                   "n_lengths": 12, "preroll_s": 0.5, "timeout_s": 30.0,
                   "prompt_len": {"dist": "lognormal", "median": 10,
                                  "sigma": 0.6, "min": 3, "max": 32},
                   "answer_len": {"dist": "lognormal", "median": 5,
                                  "sigma": 0.5, "min": 2, "max": 8}}, f)
    with open(os.path.join(base, "workloads", "tiny-granite-chat.json"),
              "w") as f:
        json.dump({"name": "tiny-granite-chat", "job": "generate",
                   "chips": 1,
                   "deployment": {"slots": 3, "cache_len": 40,
                                  "length_buckets": [8, 16, 32],
                                  "route": "/generate"},
                   "model": {"dtype": "float32", "use_flash": False},
                   "reference": {"prompt_lengths": [5, 12, 32],
                                 "max_new_tokens": 6}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-granite", "source": "tests only",
        "file": "benchmark/configs/tiny-granite.json", "reduced": [],
        "why": "a toy of the stack"})
    data["workloads"].append({
        "name": "tiny-granite-chat", "config": "tiny-granite",
        "traffic": "tiny-chat", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-granite-chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_generates_through_the_engine_and_is_correct(
        granite_root, runtime):
    result = harness.run_cell("tiny-granite-chat", SEED, 1.0, False,
                              root=granite_root, require_tpu=False)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_traced_tiny_cell_reads_the_engines_spans(granite_root, runtime):
    """On the CPU there is no device plane: the readers of the device's
    trace return nothing and the line leaves their metrics out; the engine's
    own spans are read."""
    result = harness.run_cell("tiny-granite-chat", SEED, 1.0, True,
                              root=granite_root, require_tpu=False)
    assert set(result["metrics"]) == {"slots_occupied_mean.granite",
                                      "admit_wait_ms.granite", *SETUP}
    assert 1.0 <= result["metrics"]["slots_occupied_mean.granite"][
        "value"] <= 3.0


@pytest.mark.parametrize("fault", [None, "no_skip", "bfloat16_state"])
def test_the_comparison_passes_the_program_and_fails_a_planted_fault(
        fault, monkeypatch):
    """The comparison that decides ``correct``, on the job's own slot model
    behind an engine (no proxy): the program as built reads float32 rounding;
    one that leaves ``D x`` out, and one that keeps its recurrent state in
    bfloat16, read over a limit between the two (both sides are float32
    here)."""
    import threading

    from ray_tpu.models import transformer
    from ray_tpu.serve.generation import GenerationEngine
    if fault == "no_skip":
        right = transformer._mamba_output
        monkeypatch.setattr(
            transformer, "_mamba_output",
            lambda p, y, x, z, cfg: right(p, y, jnp.zeros_like(x), z, cfg))
    elif fault == "bfloat16_state":
        step, scan = transformer.ssd.ssd_step, transformer.ssd.ssd_fwd

        def rounded(y, state):
            # not a pair of converts: the TPU compiler folds those away
            return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)

        monkeypatch.setattr(transformer.ssd, "ssd_step",
                            lambda *a: rounded(*step(*a)))
        monkeypatch.setattr(transformer.ssd, "ssd_fwd",
                            lambda *a, **k: rounded(*scan(*a, **k)))
    model = generate_job._generator_class()(
        "direct", TINY_GRANITE, TINY_DIMS,
        {"dtype": "float32", "use_flash": False},
        {"slots": 3, "cache_len": 40, "length_buckets": [8, 16, 32]}, SEED,
        False)
    engine = GenerationEngine(model, "direct", "direct-engine")
    prompts = [traffic.prompt_tokens(SEED, i, n, 96)
               for i, n in enumerate((5, 12, 32))]
    replies = [None] * 3
    model.watch(prompts, 6)

    def call(i):
        replies[i] = engine.submit({"prompt": prompts[i],
                                    "max_new_tokens": 6})

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    engine.shutdown()
    states = [np.asarray(model.kept[i]) for i in range(3)]
    check = generate_job.compare(replies, states, prompts, 6,
                                 granite_decoder, TINY_DIMS, SEED,
                                 jax.devices()[0])
    assert len(check["rows"]) == 3
    assert (check["worst"] > 3e-7) == (fault is not None), check
    assert (check["state_worst"] > 1e-5) == (fault is not None), check
