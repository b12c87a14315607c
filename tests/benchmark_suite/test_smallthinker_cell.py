"""SmallThinker's cell (``smallthinker-serve-mixed``): its files, its adapter
and streamed reference, its counts against the issue's hand counts, the
traffic's cycle, and a tiny copy of the cell through ``serve.run`` and the
generation engine, with the comparison of the K rows and its planted faults:
at toy sizes on the CPU."""

import json
import os

import jax
import numpy as np
import pytest

import benchmark_tiny
from benchmark import (generate_job, generate_kv_job, harness, manifest,
                       reducers, smallthinker_counts, smallthinker_reference,
                       traffic)
from benchmark.adapters import smallthinker_decoder
from test_benchmark_manifest import ROOTS, real_root

CELL = "smallthinker-serve-mixed"
CONFIG = "smallthinker-21b-a3b"
SEED = 2**31 + 55
LAYOUT = [0, 1, 1, 1] * 13
# the catalog row's ``config``, key for key
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
# d 48, 4 query heads over 2 K/V heads of 16 (not 48 / 4), 8 experts of 24
# and 4 a token, a window of 8, one period and a half
TINY = {
    **PUBLISHED, "name": "tiny-smallthinker", "source": "tests only",
    "adapter": "benchmark.adapters.smallthinker_decoder",
    "head_dim": 16, "hidden_size": 48, "moe_ffn_hidden_size": 24,
    "moe_num_active_primary_experts": 4, "moe_num_primary_experts": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 6, "rope_layout": [0, 1, 1, 1, 0, 1],
    "sliding_window_layout": [0, 1, 1, 1, 0, 1], "sliding_window_size": 8,
    "vocab_size": 96,
    "reduced": {"generate_kv.1": {"why": "tests"}},
}
TINY_DIMS = smallthinker_decoder.dims(TINY, "generate_kv", 1)
M, K, E = "Model", "Kernel", "Expert layer"
G, A = "Serve: generation engine", "Entry: serve API"
METRICS = {
    "decode_step_device_ms.granite": ("device_trace", M),
    "prefill_device_ms.granite": ("device_trace", M),
    "decode_share_pct.smallthinker": ("device_trace", M),
    "window_mfu_pct.smallthinker": ("device_trace", M),
    "decode_hbm_roofline_pct.smallthinker": ("device_trace", M),
    "swa_attn_roofline_pct.smallthinker": ("device_trace", K),
    "expert_matmul_roofline_pct.smallthinker": ("device_trace", E),
    "swa_attn_share_pct.smallthinker": ("device_trace", M),
    "global_attn_share_pct.smallthinker": ("device_trace", M),
    "moe_share_pct.lfm2": ("device_trace", M),
    "unscoped_share_pct.lfm2": ("device_trace", M),
    "expert_load_max_over_mean.longcat": ("program_counter", E),
    "cache_live_pct.smallthinker": ("program_span", G),
    "admit_wait_ms.granite": ("program_span", G),
    "step_host_gap_ms.smallthinker": ("device_trace", G),
    "serve_startup_s.serve": ("host_clock", A),
    "expert_share_pct.lfm2": ("device_trace", E),
}
# PR 60 folded the entries that repeat another's reader into the first of
# their kind (Granite's step, prefill and admission; LFM2's shares; LongCat's
# load; the serving cells' start-up): nine of the seventeen are the cell's own
SETUP = ("serve_startup_s.serve",)
OWN = [name for name in METRICS if name.endswith(".smallthinker")]


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
    # the eleventh cell, after the accepted ten, of which one takes four
    # chips (the quota is max(1, 11 // 4) = 2)
    names = real.cell_names()
    assert names.index(CELL) == 10 == 1 + names.index("granite4h-serve-chat")
    assert [w["name"] for w in real.data["workloads"][:11]
            if w["chips"] == 4] == ["mistral7b-train-4k-fsdp4"]
    entry = real.data["configs"][8]
    assert entry["name"] == CONFIG
    assert entry["reduced"] == ["num_hidden_layers"]
    throughput = next(m for m in real.data["end_to_end"]
                      if m["name"] == "serve_tokens_per_s")
    assert throughput["workloads"][5] == CELL and throughput["bound"] == 0.06
    assert os.path.getsize(os.path.join(real.root, "BENCHMARK.json")) < 2**16


def test_the_cell_reports_throughput_set_up_and_its_metrics(real):
    cell = real.cell(CELL)
    assert cell.job == "generate_kv" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"]: (m["source"], m["layer"])
            for m in cell.per_layer} == METRICS
    for m in cell.per_layer:
        assert CELL in m["workloads"]
        assert (m["workloads"] == [CELL]) == (m["name"] in OWN)
        assert m["moves"] == ("setup_s" if m["name"] in SETUP
                              else "serve_tokens_per_s")
        assert callable(reducers.resolve(m["reducer"]))
    # the cell's own entries stand together, in this order
    names = [m["name"] for m in real.data["per_layer"]]
    first = names.index("decode_share_pct.smallthinker")
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


def test_the_configuration_keeps_every_published_key_and_cuts_the_depth(real):
    config = real.cell(CELL).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["adapter"] == "benchmark.adapters.smallthinker_decoder"
    cut = config["reduced"]["generate_kv.1"]
    assert set(cut) == {"num_hidden_layers", "published_layers", "why",
                        "deployment", "slots_rule", "slots_read"}
    assert cut["published_layers"] == list(range(8))
    assert len(config["assumed"]) >= 8 and len(config["departures"]) == 5
    dims = smallthinker_decoder.dims(config, "generate_kv", 1)
    assert dims["n_layers"] == 8 and dims["vocab_size"] == 151936
    assert dims["layer_types"] == ["global", "window", "window",
                                   "window"] * 2
    assert (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
            dims["head_dim"], dims["expert_width"], dims["n_experts"],
            dims["top_k"], dims["window"]) == (2560, 28, 4, 128, 768, 64, 6,
                                               4096)
    with pytest.raises(manifest.ManifestError, match="no 'reduced' entry"):
        smallthinker_decoder.dims(config, "generate", 1)
    cfg = smallthinker_decoder.program_config(dims, 13312, {})
    assert cfg.head_dim == 128 != cfg.d_model // cfg.n_heads
    assert cfg.experts.activation == "relu" and cfg.experts.all_held
    assert (cfg.experts.score, cfg.experts.normalize) == ("softmax", True)


@pytest.mark.parametrize("change, says", [
    ({"moe_primary_router_apply_softmax": False},
     "moe_primary_router_apply_softmax"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_layout": [1, 1, 1, 1, 0, 1]}, "are not one list"),
])
def test_a_layer_the_program_does_not_have_is_refused_by_name(change, says):
    with pytest.raises(manifest.ManifestError, match=says):
        smallthinker_decoder.dims({**TINY, **change}, "generate_kv", 1)


def test_a_program_without_the_two_kinds_is_refused_before_a_chip(
        monkeypatch):
    from ray_tpu.models import transformer
    monkeypatch.delattr(transformer, "WINDOW_MOE")
    with pytest.raises(manifest.ManifestError, match="no window attention"):
        smallthinker_decoder.dims(TINY, "generate_kv", 1)


def test_the_traffic_and_the_deployment_are_the_issues(real):
    cell = real.cell(CELL)
    mix, opts = cell.traffic, cell.deploy["deployment"]
    assert (mix["loop"], mix["clients"], mix["preroll_s"],
            mix["timeout_s"]) == ("closed", 48, 10.0, 120.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 1.0, "min": 128, "max": 12288}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "min": 64, "max": 1024}
    assert (opts["slots"], opts["cache_len"], opts["length_buckets"]) == (
        48, 13312, [512, 1024, 2048, 4096, 8192, 12288])
    assert opts["cache_len"] == mix["prompt_len"]["max"] \
        + mix["answer_len"]["max"]
    assert cell.deploy["reference"] == {"prompt_lengths": [512, 3968, 12288],
                                        "max_new_tokens": 384}
    assert set(mix) == {"name", "kind", "loop", "pattern_seed",
                        "answer_pattern_seed", "clients", "n_lengths",
                        "arrange", "preroll_s", "prompt_len", "answer_len",
                        "timeout_s", "why"}
    # the issue's rule: the smallest multiple of the 48 callers that holds
    # what a window answers (94 to 101 replies on the chip)
    assert mix["n_lengths"] == 144 == 3 * mix["clients"]


def test_every_seed_sends_the_same_cycle_from_another_place(real):
    mix = real.cell(CELL).traffic
    plans = [generate_job.request_plan(mix, seed)
             for seed in (0, 7, 2**31 + 5)]
    pairs = [sorted(zip(p["lengths"], p["answers"])) for p in plans]
    assert pairs[0] == pairs[1] == pairs[2]
    assert len({tuple(p["lengths"]) for p in plans}) > 1
    first = plans[0]
    assert len(first["lengths"]) == len(first["answers"]) == mix["n_lengths"]
    assert min(first["lengths"]) >= 128 and max(first["lengths"]) <= 12288
    assert min(first["answers"]) >= 64 and max(first["answers"]) <= 1024
    assert abs(float(np.median(first["lengths"])) - 3072) <= 40
    assert abs(float(np.median(first["answers"])) - 384) <= 5
    # about two in five longer than the window, one in five under 1,400
    longer = np.mean(np.array(first["lengths"]) > 4096)
    assert 0.33 <= longer <= 0.45
    assert 0.17 <= np.mean(np.array(first["lengths"]) < 1400) <= 0.25
    assert abs(np.corrcoef(first["lengths"], first["answers"])[0, 1]) < 0.2


# -- the reference and the counts ---------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_the_streamed_draw_is_init_params_leaf_for_leaf(seed):
    from ray_tpu.models import transformer
    cfg = smallthinker_decoder.program_config(TINY_DIMS, 64,
                                              {"dtype": "float32"})
    key = harness.prng_key(seed)
    ours = jax.jit(lambda k: transformer.init_params(k, cfg))(key)
    theirs = jax.jit(lambda k: smallthinker_reference.draw_tree(
        k, TINY_DIMS))(key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    jax.tree.map(np.testing.assert_array_equal, ours, theirs)


def test_counts_at_the_published_sizes_are_the_issues(real):
    dims = smallthinker_decoder.dims(real.cell(CELL).config, "generate_kv", 1)
    c = smallthinker_counts
    assert c.layers(dims) == (6, 2)
    # attention 20.97 M, router 0.16 M, 64 experts 377.49 M: 398.6 M a layer
    assert c.attention_params(dims) == 2560 * 3584 * 2 + 2560 * 512 * 2
    assert c.attention_params(dims) == pytest.approx(20.97e6, rel=1e-3)
    assert 64 * c.expert_params(dims) == pytest.approx(377.49e6, rel=1e-4)
    assert c.layer_params(dims) == pytest.approx(398.6e6, rel=1e-3)
    # whole: 52 layers and 777.9 M of embedding and head, 21.5 B; the cut
    # 3.967 B, 7.93 GB
    assert 52 * c.layer_params(dims) + 2 * 151936 * 2560 == pytest.approx(
        21.5e9, rel=2e-3)
    assert c.param_count(dims) == pytest.approx(3.967e9, rel=1e-3)
    assert c.param_count(dims) * 2 == pytest.approx(7.93e9, rel=1e-3)
    from ray_tpu.models import transformer
    cfg = smallthinker_decoder.program_config(dims, 13312, {})
    shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert c.param_count(dims) == sum(int(np.prod(s.shape))
                                      for s in jax.tree.leaves(shapes))
    # a K/V row 2,048 B a position a layer; a slot 54.5 + 50.3 = 104.9 MB;
    # 48 slots 5.03 GB
    assert c.row_bytes(dims) == 2048
    assert 2 * 13312 * 2048 == pytest.approx(54.5e6, rel=1e-3)
    assert 6 * 4096 * 2048 == pytest.approx(50.3e6, rel=1e-3)
    assert c.slot_bytes(13312, dims) == pytest.approx(104.9e6, rel=1e-3)
    state = jax.eval_shape(
        lambda: transformer.init_decode_state(cfg, 48, 13312))
    assert 48 * c.slot_bytes(13312, dims) == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(state)) - 48 * 4
    assert 48 * c.slot_bytes(13312, dims) == pytest.approx(5.03e9, rel=1e-3)
    # one cache of 13,312 rows for all 8 layers: 218 MB a slot
    assert 8 * 13312 * 2048 == pytest.approx(218e6, rel=1e-3)
    # a step's least: 7.15 GB of weights (8 layers and the head) and the
    # rows the slots hold
    assert c.step_weight_bytes(dims) == pytest.approx(7.156e9, rel=1e-3)
    assert c.decode_step_bytes(48 * c.slot_rows(13312, dims), dims) \
        == pytest.approx(7.156e9 + 5.03e9, rel=1e-3)
    # the band: a prompt no longer than the window is the triangle, a longer
    # one the triangle and W keys a position past it
    assert c.band_pairs(4096, 4096) == 4096 * 4097 // 2
    assert c.band_pairs(100, 4096) == 100 * 101 // 2
    assert c.band_pairs(12288, 4096) == 4096 * 4097 // 2 + 8192 * 4096
    assert c.band_pairs(12288, 4096) < 12288 * 12289 // 2 * 0.56
    # a token: 113 MFLOP a layer outside the scores
    assert c.token_flops(dims) == pytest.approx(
        2 * (20.97e6 + 0.164e6 + 6 * 5.898e6), rel=1e-3)
    assert c.prefill_flops(1, 4096, dims) > 4096 * 8 * c.token_flops(dims)
    assert c.decode_step_flops(48, 0, dims) == pytest.approx(
        48 * (8 * c.token_flops(dims) + 2 * 2560 * 151936), rel=1e-6)


# -- a tiny copy of the cell, through serve.run and the engine ------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: new files and entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("smallthinker"),
                                    cells=("tiny-serve-closed",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-smallthinker.json"),
              "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(base, "traffic", "tiny-mixed.json"), "w") as f:
        json.dump({"name": "tiny-mixed", "kind": "requests", "loop": "closed",
                   "pattern_seed": 1, "answer_pattern_seed": 2, "clients": 3,
                   "n_lengths": 12, "preroll_s": 0.5, "timeout_s": 30.0,
                   "prompt_len": {"dist": "lognormal", "median": 10,
                                  "sigma": 0.8, "min": 3, "max": 32},
                   "answer_len": {"dist": "lognormal", "median": 6,
                                  "sigma": 0.5, "min": 2, "max": 12}}, f)
    with open(os.path.join(base, "workloads", "tiny-smallthinker-mixed.json"),
              "w") as f:
        json.dump({"name": "tiny-smallthinker-mixed", "job": "generate_kv",
                   "chips": 1,
                   "deployment": {"slots": 3, "cache_len": 44,
                                  "length_buckets": [4, 16, 32],
                                  "route": "/generate"},
                   "model": {"dtype": "float32", "use_flash": False},
                   "reference": {"prompt_lengths": [3, 12, 32],
                                 "max_new_tokens": 12}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-smallthinker", "source": "tests only",
        "file": "benchmark/configs/tiny-smallthinker.json",
        "reduced": ["num_hidden_layers"], "why": "a toy of the stack"})
    data["workloads"].append({
        "name": "tiny-smallthinker-mixed", "config": "tiny-smallthinker",
        "traffic": "tiny-mixed", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-smallthinker-mixed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_generates_through_the_engine_and_is_correct(
        tiny_root, runtime):
    result = harness.run_cell("tiny-smallthinker-mixed", SEED, 1.0, False,
                              root=tiny_root, require_tpu=False)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_traced_tiny_cell_reads_the_engines_spans(tiny_root, runtime):
    """On the CPU there is no device plane: the readers of the device's
    trace return nothing and the line leaves their metrics out; the engine's
    own spans are read, ``live_rows`` among their attributes, and the
    mixtures' loads reach the counters from prefill and from every step."""
    result = harness.run_cell("tiny-smallthinker-mixed", SEED, 1.0, True,
                              root=tiny_root, require_tpu=False)
    assert set(result["metrics"]) == {
        "admit_wait_ms.granite", "cache_live_pct.smallthinker",
        "expert_load_max_over_mean.longcat", *SETUP}
    assert result["metrics"]["admit_wait_ms.granite"]["value"] >= 0.0
    assert 0.0 < result["metrics"]["cache_live_pct.smallthinker"][
        "value"] <= 100.0
    assert result["metrics"]["expert_load_max_over_mean.longcat"][
        "value"] >= 1.0


def _direct(monkeypatch, fault):
    """The comparison that decides ``correct``, on the job's own slot model
    behind an engine (no proxy), with ``fault`` planted in the program."""
    import threading

    from ray_tpu.models import transformer
    from ray_tpu.serve.generation import GenerationEngine
    planted = {
        "router_after_attention": ("EARLY_ROUTED", ()),
        "window_layer_sees_everything": (
            "_kind_attention",
            lambda cfg, kind: (kind == transformer.WINDOW_MOE, None)),
        "ring_one_row_off": ("_ring_row", lambda p, rows: (p + 1) % rows),
    }
    if fault is not None:
        monkeypatch.setattr(transformer, *planted[fault])
    model = generate_kv_job._generator_class()(
        "direct", TINY, TINY_DIMS, {"dtype": "float32", "use_flash": False},
        {"slots": 3, "cache_len": 44, "length_buckets": [4, 16, 32]}, SEED,
        False)
    engine = GenerationEngine(model, "direct", "direct-engine")
    prompts = [traffic.prompt_tokens(SEED, i, n, 96)
               for i, n in enumerate((3, 12, 32))]
    replies = [None] * 3
    model.watch(prompts, 12)

    def call(i):
        replies[i] = engine.submit({"prompt": prompts[i],
                                    "max_new_tokens": 12})

    threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    engine.shutdown()
    kept = [tuple(np.asarray(a) for a in model.kept[i]) for i in range(3)]
    return generate_kv_job.compare(replies, kept, prompts, 12,
                                   smallthinker_decoder, TINY_DIMS, SEED,
                                   jax.devices()[0])


@pytest.mark.parametrize("fault", [None, "router_after_attention",
                                   "window_layer_sees_everything",
                                   "ring_one_row_off"])
def test_the_comparison_passes_the_program_and_fails_a_planted_fault(
        fault, monkeypatch):
    """The program as built reads float32 rounding on both numbers (both
    sides are float32 here); the router read after the attention and a
    window layer that sees everything move the logits, and a ring written
    one row off moves the K rows found at their positions."""
    check = _direct(monkeypatch, fault)
    assert len(check["rows"]) == 3
    assert [r["held"] for r in check["rows"]] == [14, 23, 43]
    assert all(len(r["cache_err"]) == len(r["cache_norm"]) == 6
               and all(len(groups) == 2 for groups in r["cache_err"])
               for r in check["rows"])
    if fault is None:
        assert check["worst"] < 1e-4 and check["cache_worst"] < 1e-4, check
        # float32 on both sides: nothing flips, and the clear rows (a
        # quarter to all of a layer's) read rounding at their largest
        assert check["clear_worst"] < 1e-4, check
        assert check["clear_steps_worst"] < 1e-4, check
        assert check["placed_worst"] == 1.0, check
    elif fault == "ring_one_row_off":
        assert check["placed_worst"] < 0.5, check
        # a ring's clear rows that the steps wrote are their neighbours'
        assert check["clear_steps_worst"] > 0.1, check
    else:
        assert check["worst"] > 1e-2, check
        assert check["clear_worst"] > 0.1, check


def _kept_of(ref_keys, held, window, dims):
    """What a replica would keep of a sequence whose K rows are
    ``ref_keys`` [L, S, kv]: the global layers' rows and the window layers'
    rings, position ``p`` in row ``p % window``."""
    rows = np.stack([ref_keys[i][:held] for i, k in
                     enumerate(dims["layer_types"]) if k != "window"])
    at = np.arange(held - window, held)
    rings = np.zeros((sum(k == "window" for k in dims["layer_types"]),
                      window, ref_keys.shape[-1]))
    for j, i in enumerate(i for i, k in enumerate(dims["layer_types"])
                          if k == "window"):
        rings[j][at % window] = ref_keys[i][at]
    return rows, rings, np.int32(held)


@pytest.mark.parametrize("touched, clear, steps", [
    ("none", False, False),              # nothing is off
    ("prompt_row", True, False),         # one clear row of the prompt's
    ("prompt_row_narrow_below", False, False),  # ... that a flip may reach
    ("prompt_row_narrow_here", True, False),    # its own layer's cannot
    ("layer_0_step", True, False),       # layer 0: every row is clear
    ("one_step", False, False),          # one of five clear rows of a step
    ("two_steps", False, True),          # two of five: the upper quartile
    ("two_steps_narrow_below", False, False),
])
def test_clear_rows_are_held_at_their_largest_and_their_upper_quartile(
        touched, clear, steps):
    """``cache_error``'s ``clear`` and ``clear_steps``: rows of layer 1 (a
    window layer) are moved by a half; they count unless the router *below*
    layer 1 chose that token by less than the margin."""
    dims = {"layer_types": ["global", "window", "window", "global"]}
    held, window, prompt = 20, 8, 15
    rng = np.random.default_rng(0)
    ref = rng.normal(size=(4, held + 1, 6))
    margins = np.full((4, held + 1), 0.5)
    # layers 2 and 3 have no clear row a step wrote: layer 1's five stand
    # alone
    margins[1, prompt:] = 0.05
    rows, rings, n = _kept_of(ref, held, window, dims)
    moved = {"none": [], "layer_0_step": [], "one_step": [16],
             "two_steps": [16, 18], "two_steps_narrow_below": [16, 18]}.get(
                 touched, [13])
    for p in moved:
        rings[0][p % window] += 0.5 * ref[1][p]
    if touched == "layer_0_step":
        rows[0][17] += 0.5 * ref[0][17]
    if touched.endswith("narrow_below"):
        margins[0, moved] = 0.05
    if touched.endswith("narrow_here"):
        margins[1, moved] = 0.05
    off = generate_kv_job.cache_error((rows, rings, n), ref, margins, 0.1,
                                      dims, prompt)
    assert off["held"] == held and off["placed"] == 1.0
    assert (off["clear"] > 0.4) == clear, off
    assert (off["clear_steps"] > 0.4) == steps, off
    assert off["clear_steps_n"] == (
        3 if touched == "two_steps_narrow_below" else 5)
    assert off["worst"] < 1e-9      # the prefill's rows' quartile sees none
    # layer 0 has no mixture below it: every row is clear
    assert off["witness"][0][0] == 1.0
    if touched == "prompt_row_narrow_below":
        assert off["witness"][1][0] < 1.0 and off["witness"][1][2] > 0.0
