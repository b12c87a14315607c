"""Phi-4-mini-flash-reasoning's cell (``phi4flash-serve-reason``): its files,
its adapter and streamed reference, its counts against the issue's hand
counts and the traffic's cycle, at toy sizes on the CPU (a tiny copy of the
cell through ``serve.run`` and the engine is ``test_phi4flash_engine.py``'s)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (generate_job, harness, manifest, phi4flash_counts,
                       phi4flash_reference, reducers)
from benchmark.adapters import phi4flash_decoder
from test_benchmark_manifest import ROOTS, real_root

CELL = "phi4flash-serve-reason"
CONFIG = "phi-4-mini-flash-reasoning"
TRAFFIC = "reason256x768-closed96"
SEED = 2**31 + 61
# the catalog row's ``config``, key for key
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
# d 32, 8 query heads over 4 K/V heads of 4, 64 channels against a state of 4,
# a window of 8, eight layers: every kind of the stack
TINY_PHI = {
    **PUBLISHED, "name": "tiny-phi4flash", "source": "tests only",
    "adapter": "benchmark.adapters.phi4flash_decoder",
    "hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 8,
    "num_key_value_heads": 4, "num_hidden_layers": 8, "sliding_window": 8,
    "vocab_size": 96,
    "assumed": {"mamba_sizes": {"d_state": 4, "d_conv": 4, "expand": 2,
                                "dt_rank": 2}},
    "reduced": {"generate.1": {"why": "tests"}},
}
TINY_DIMS = phi4flash_decoder.dims(TINY_PHI, "generate", 1)
OWN = {
    "window_mfu_pct.phi4flash": ("device_trace", "Model"),
    "decode_hbm_roofline_pct.phi4flash": ("device_trace", "Model"),
    "diff_attn_roofline_pct.phi4flash": ("device_trace", "Kernel"),
    "mamba1_step_roofline_pct.phi4flash": ("device_trace", "Kernel"),
    "mamba1_prefill_roofline_pct.phi4flash": ("device_trace", "Kernel"),
    "cross_share_pct.phi4flash": ("device_trace", "Model"),
    "gmu_share_pct.phi4flash": ("device_trace", "Model"),
    "cache_live_pct.phi4flash": ("program_span", "Serve: generation engine"),
}
# the accepted entries whose readers read this cell as they are: one entry a
# reader (PR 60's fold), so the cell is appended to their lists. Granite's and
# SmallThinker's cell tests pin six of those lists to their cell alone
# (``mamba_share_pct``, ``head_share_pct``, ``slots_occupied_mean``;
# ``decode_share_pct``, ``swa_attn_share_pct``, ``step_host_gap_ms``) and
# fail on any later cell that joins: a ``benchmark`` PR's to repair (PERF.md
# section 7)
JOINED = {
    "serve_startup_s.serve": ("host_clock", "Entry: serve API"),
    "decode_step_device_ms.granite": ("device_trace", "Model"),
    "prefill_device_ms.granite": ("device_trace", "Model"),
    "admit_wait_ms.granite": ("program_span", "Serve: generation engine"),
    "unscoped_share_pct.lfm2": ("device_trace", "Model"),
    "mlp_share_pct.lfm2": ("device_trace", "Model"),
    "mamba_share_pct.granite": ("device_trace", "Model"),
    "head_share_pct.granite": ("device_trace", "Model"),
    "slots_occupied_mean.granite": ("program_span",
                                    "Serve: generation engine"),
    "decode_share_pct.smallthinker": ("device_trace", "Model"),
    "swa_attn_share_pct.smallthinker": ("device_trace", "Model"),
    "step_host_gap_ms.smallthinker": ("device_trace",
                                      "Serve: generation engine"),
}


@pytest.fixture(scope="module", params=ROOTS)
def real(request, tmp_path_factory):
    return manifest.Manifest(real_root(request.param, tmp_path_factory))


# -- the files ---------------------------------------------------------------------------


def test_the_manifest_is_clean_and_holds_the_cell_at_its_end(real):
    assert manifest.check(real) == []
    # the twelfth cell, after the accepted eleven, of which one takes four
    # chips
    names = real.cell_names()
    assert names.index(CELL) == 11 == 1 + names.index(
        "smallthinker-serve-mixed")
    assert [w["name"] for w in real.data["workloads"][:12]
            if w["chips"] == 4] == ["mistral7b-train-4k-fsdp4"]
    entry = real.data["configs"][9]
    assert entry["name"] == CONFIG and entry["reduced"] == []
    throughput = next(m for m in real.data["end_to_end"]
                      if m["name"] == "serve_tokens_per_s")
    assert CELL in throughput["workloads"] and throughput["bound"] == 0.06
    names = [m["name"] for m in real.data["per_layer"]]
    assert names.index("window_mfu_pct.phi4flash") == 97
    assert len(names) <= 128


def test_the_cell_reports_throughput_set_up_and_its_twenty_metrics(real):
    assert len(OWN) == 8 and len(JOINED) == 12
    cell = real.cell(CELL)
    assert cell.job == "generate" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"]: (m["source"], m["layer"])
            for m in cell.per_layer} == {**OWN, **JOINED}
    for m in cell.per_layer:
        assert CELL in m["workloads"]
        assert (m["workloads"][0] == CELL) == (m["name"] in OWN)
        assert m["moves"] == ("setup_s" if m["name"].startswith("serve_")
                              else "serve_tokens_per_s")
        assert callable(reducers.resolve(m["reducer"]))
    # the cell's own entries stand together, in this order
    names = [m["name"] for m in real.data["per_layer"]]
    first = names.index("window_mfu_pct.phi4flash")
    assert names[first:first + len(OWN)] == list(OWN)


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_metrics_file_agrees_with_its_entry(real, name):
    entry = next(m for m in real.data["per_layer"] if m["name"] == name)
    with open(os.path.join(real.dir, "layer_metrics", name + ".json")) as f:
        held = json.load(f)
    # a later cell like this one is appended to an entry's list
    assert {k: held[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"][:len(held["workloads"])] == held["workloads"]
    # a gated memory unit's share is the accepted reader with a scope of its
    # own; the others are this configuration's counts
    assert held["what"] and held["reducer"] == (
        "benchmark.lfm2_counts:scope_share_pct"
        if name == "gmu_share_pct.phi4flash"
        else "benchmark.phi4flash_counts:" + name.split(".")[0])
    if "roofline" in name or "mfu" in name or "share" in name:
        assert held["unit"] == "%"


def test_the_configuration_keeps_every_published_key_and_cuts_nothing(real):
    config = real.cell(CELL).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["adapter"] == "benchmark.adapters.phi4flash_decoder"
    assert set(config["reduced"]["generate.1"]) == {"why", "slots"}
    assert config["assumed"]["mamba_sizes"] == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    assert len(config["assumed"]) >= 12 and len(config["departures"]) == 4
    dims = phi4flash_decoder.dims(config, "generate", 1)
    assert dims["n_layers"] == 32 and dims["vocab_size"] == 200064
    assert (dims["d_inner"], dims["d_state"], dims["dt_rank"],
            dims["head_dim"], dims["window"]) == (5120, 16, 160, 64, 512)
    types = dims["layer_types"]
    assert types[:16] == ["mamba", "swa"] * 8
    assert types[16:18] == ["mamba", "full"]
    assert types[18:] == ["gmu", "cross"] * 7
    with pytest.raises(manifest.ManifestError, match="no 'reduced' entry"):
        phi4flash_decoder.dims(config, "serve", 1)


@pytest.mark.parametrize("change, says", [
    ({"model_type": "phi3"}, "model_type"),
    ({"mb_per_layer": 4}, "mb_per_layer"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"assumed": {}}, "mamba_sizes"),
])
def test_a_layer_the_program_does_not_have_is_refused_by_name(change, says):
    with pytest.raises(manifest.ManifestError, match=says):
        phi4flash_decoder.dims({**TINY_PHI, **change}, "generate", 1)


def test_a_program_without_the_kinds_is_refused_before_a_chip(monkeypatch):
    from ray_tpu.models import transformer
    monkeypatch.delattr(transformer, "GMU")
    with pytest.raises(manifest.ManifestError, match="gated memory unit"):
        phi4flash_decoder.dims(TINY_PHI, "generate", 1)


def test_the_traffic_and_the_deployment_are_the_issues(real):
    cell = real.cell(CELL)
    mix, opts = cell.traffic, cell.deploy["deployment"]
    assert (mix["loop"], mix["clients"], mix["preroll_s"], mix["timeout_s"],
            mix["arrange"]) == ("closed", 96, 20.0, 120.0, "by_client")
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 64, "max": 1024}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.5, "min": 192, "max": 2048}
    assert (opts["slots"], opts["cache_len"], opts["length_buckets"]) == (
        96, 3072, [128, 256, 512, 1024])
    assert opts["cache_len"] == mix["prompt_len"]["max"] \
        + mix["answer_len"]["max"]
    # the compared answers are as long as the traffic's longest, at three
    # prompt lengths of three buckets
    sample = cell.deploy["reference"]
    assert sample["max_new_tokens"] == mix["answer_len"]["max"]
    assert len({min(b for b in opts["length_buckets"] if b >= n)
                for n in sample["prompt_lengths"]}) == 3
    assert mix["n_lengths"] % mix["clients"] == 0
    assert mix["pattern_seed"] != mix["answer_pattern_seed"]


def test_every_seed_sends_the_same_cycle_from_another_place(real):
    mix = real.cell(CELL).traffic
    plans = [generate_job.request_plan(mix, seed)
             for seed in (0, 7, 2**31 + 5)]
    pairs = [sorted(zip(p["lengths"], p["answers"])) for p in plans]
    assert pairs[0] == pairs[1] == pairs[2]
    first = plans[0]
    assert len(first["lengths"]) == len(first["answers"]) == mix["n_lengths"]
    assert min(first["lengths"]) >= 64 and max(first["lengths"]) <= 1024
    assert min(first["answers"]) >= 192 and max(first["answers"]) <= 2048
    assert abs(float(np.median(first["lengths"])) - 256) <= 4
    assert abs(float(np.median(first["answers"])) - 768) <= 8
    # the two arrangements are not one: long prompts do not get long answers
    assert abs(np.corrcoef(first["lengths"], first["answers"])[0, 1]) < 0.25


# -- the reference and the counts ---------------------------------------------------------


@pytest.mark.parametrize("seed", [2**31 + 7])
def test_the_streamed_draw_is_init_params_leaf_for_leaf(seed):
    from ray_tpu.models import transformer
    cfg = phi4flash_decoder.program_config(TINY_DIMS, 64,
                                           {"dtype": "float32"})
    key = harness.prng_key(seed)
    ours = jax.jit(lambda k: transformer.init_params(k, cfg))(key)
    theirs = jax.jit(lambda k: phi4flash_reference.draw_tree(k, TINY_DIMS))(
        key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    jax.tree.map(np.testing.assert_array_equal, ours, theirs)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(phi4flash_reference))
    names = [n.module if isinstance(n, ast.ImportFrom) else a.name
             for n in ast.walk(tree)
             if isinstance(n, (ast.Import, ast.ImportFrom))
             for a in n.names]
    assert not [n for n in names if n.startswith(("ray_tpu", "benchmark"))]


def test_the_streamed_logits_and_states_are_the_whole_trees():
    key = harness.prng_key(SEED)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 20), 0, 96)
    tree = jax.jit(lambda k: phi4flash_reference.draw_tree(k, TINY_DIMS))(key)
    whole, states, _ = jax.jit(
        lambda tree, t: phi4flash_reference.tree_forward(
            tree, t, TINY_DIMS, last=13))(tree, tokens[0])
    streamed_fn = jax.jit(
        lambda k, t, first: phi4flash_reference.logits_and_state_from(
            k, t, first, 5, TINY_DIMS))
    streamed, kept = streamed_fn(key, tokens[0], 9)
    np.testing.assert_allclose(streamed, whole[9:14], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(kept, states, rtol=1e-4, atol=1e-6)
    assert kept.shape == (3, 64, 4)
    # the adapter hands the states on laid as the program keeps them
    _, laid = jax.jit(lambda k, t: phi4flash_decoder.logits_and_state_from(
        k, t, 9, 5, TINY_DIMS))(key, tokens[0])
    np.testing.assert_allclose(laid, states.swapaxes(1, 2), rtol=1e-4,
                               atol=1e-6)
    last = jax.jit(lambda k, t: phi4flash_reference.last_logits(
        k, t, TINY_DIMS))(key, tokens)
    np.testing.assert_allclose(last[0], whole[-1], rtol=1e-4, atol=1e-6)
    # positions to the right change nothing before them
    padded = jnp.concatenate([tokens[0, :14], jnp.zeros((6,), jnp.int32)])
    np.testing.assert_allclose(streamed_fn(key, padded, 9)[0][:4],
                               whole[9:13], rtol=1e-4, atol=1e-6)


def test_counts_at_the_published_sizes_are_the_issues(real):
    dims = phi4flash_decoder.dims(real.cell(CELL).config, "generate", 1)
    c = phi4flash_counts
    assert (c.count(dims, "mamba"), c.count(dims, "swa"),
            c.count(dims, "full", "cross"), c.count(dims, "gmu")) == (
                9, 8, 8, 7)
    # a Mamba mixer 41.24 M, self-attention 19.67 M, cross 13.11 M, a GMU
    # 26.21 M, an FFN 78.64 M
    assert c.mamba_params(dims) + c.mamba_small(dims) == pytest.approx(
        41.24e6, rel=1e-3)
    assert c.attention_params(dims) + c.attention_small(dims) \
        == pytest.approx(19.67e6, rel=1e-3)
    assert c.attention_params(dims, True) == pytest.approx(13.11e6, rel=1e-3)
    assert c.gmu_params(dims) == pytest.approx(26.21e6, rel=1e-3)
    assert c.ffn_params(dims) == pytest.approx(78.64e6, rel=1e-3)
    assert c.param_count(dims) == pytest.approx(3.852e9, rel=1e-3)
    from ray_tpu.models import transformer
    cfg = phi4flash_decoder.program_config(dims, 3072, {})
    shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert c.param_count(dims) == sum(int(np.prod(s.shape))
                                      for s in jax.tree.leaves(shapes))
    # a slot at 3,072 positions: 20.97 MB of rings, 15.73 of the one full
    # cache, 3.23 of states and tails: 39.9 MB
    held = c.state_bytes(1, 3072, dims)
    assert held["ring"] == 8 * 512 * 5120 and held["kv"] == 3072 * 5120
    assert held["ssm"] + held["conv"] == 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert sum(held.values()) == pytest.approx(39.9e6, rel=2e-3)
    state = jax.eval_shape(
        lambda: transformer.init_decode_state(cfg, 96, 3072))
    held = c.state_bytes(96, 3072, dims)
    assert sum(held.values()) == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(state)) - 96 * 4
    assert sum(held.values()) == pytest.approx(3.83e9, rel=2e-3)
    # a step at 96 slots of about 800 live rows: 7.70 GB of weights, 2.01 of
    # rings, 0.57 of state in and out, 3.1 of the full cache's eight reads
    live = 96 * (8 * 512 + 8 * 800)
    assert c.step_weight_bytes(dims) == pytest.approx(7.70e9, rel=1e-3)
    assert c.decode_step_bytes(96, live, dims) == pytest.approx(
        7.70e9 + 2.01e9 + 0.62e9 + 3.15e9, rel=5e-3)
    assert c.slot_rows(3072, dims) == 8 * 512 + 8 * 3072
    assert c.scan_step_bytes(96, dims) * 9 == pytest.approx(0.566e9,
                                                            rel=1e-2)
    # a prompt's cross-decoder is one position's: 14 of 32 layers
    whole = 1024 * sum(c.layer_token_flops(k, dims)
                       for k in dims["layer_types"])
    assert c.prefill_flops(1024, dims) < 0.65 * whole
    assert c.decode_step_flops(96, live, dims) == pytest.approx(
        96 * 2 * 3.852e9, rel=5e-2)
