"""LFM2's cell (``lfm2-24b-serve-prefill``): its files, its adapter and
streamed reference, its counts against hand counts, its readers on a plane
this file encodes, planted faults that the adapter's tolerance must refuse,
and a tiny copy of the cell through ``serve.run``: at toy sizes on the CPU."""

import dataclasses
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
from benchmark import (device_scopes, harness, lfm2_counts, lfm2_reference,
                       manifest, program_spans, reducers, sala_counts,
                       serve_job, trace_reduce)
from benchmark.adapters import lfm2_decoder
from benchmark.program_spans import Span
from test_benchmark_device_scopes import (T0_NS, _event, _field, _metadata,
                                          _plane, _written, BASE_NS)
from test_benchmark_manifest import ROOTS, real_root

CELL = "lfm2-24b-serve-prefill"
CONFIG = "lfm2-24b-a2b"
SEED = 2**31 + 46
PERIOD = ["full_attention", "conv", "conv", "conv"]
# the catalog row's ``config``, key for key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + 9 * PERIOD + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
KEPT = [0, 2, 3, 4, 5, 6, 7, 8, 9]
# d 64, 4 query heads over 2 K/V heads of 16, 16 experts of 32, top-4, the
# cell's own nine layers of a published stack of 12
TINY_LFM2 = {
    **PUBLISHED, "name": "tiny-lfm2", "source": "tests only",
    "adapter": "benchmark.adapters.lfm2_decoder",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 16,
    "num_hidden_layers": 12, "layer_types": PUBLISHED["layer_types"][:12],
    "vocab_size": 96,
    "reduced": {"serve.1": {"num_hidden_layers": 9, "published_layers": KEPT,
                            "why": "tests"}},
}
TINY_DIMS = lfm2_decoder.dims(TINY_LFM2, "serve", 1)
FLOAT32 = {"dtype": "float32", "use_flash": False, "remat": False}
METRICS = {
    "fwd_device_ms.lfm2": ("device_trace", "serve_tokens_per_s", "Model"),
    "fwd_mfu_pct.lfm2": ("device_trace", "serve_tokens_per_s", "Model"),
    "gqa64_attn_share_pct.lfm2": ("device_trace", "serve_tokens_per_s",
                                  "Kernel"),
    "gqa64_attn_roofline_pct.lfm2": ("device_trace", "serve_tokens_per_s",
                                     "Kernel"),
    "expert_share_pct.lfm2": ("device_trace", "serve_tokens_per_s",
                              "Expert layer"),
    "expert_matmul_roofline_pct.lfm2": ("device_trace", "serve_tokens_per_s",
                                        "Expert layer"),
    # PR 60: LongCat's reader, the offline cell's and the serving cells'
    # start-up under the first entry of each kind; the constructor's gauge
    # (``replica_init_s.lfm2``) is retired for the start-up it read
    "expert_load_max_over_mean.longcat": ("program_counter",
                                          "serve_tokens_per_s",
                                          "Expert layer"),
    "idle_batch_host_pct.offline": ("program_span", "serve_tokens_per_s",
                                    "Serve: replica batcher"),
    "serve_startup_s.serve": ("host_clock", "setup_s", "Entry: serve API"),
    "shortconv_share_pct.lfm2": ("device_trace", "serve_tokens_per_s",
                                 "Model"),
    "attn_share_pct.lfm2": ("device_trace", "serve_tokens_per_s", "Model"),
    "mlp_share_pct.lfm2": ("device_trace", "serve_tokens_per_s", "Model"),
    "moe_share_pct.lfm2": ("device_trace", "serve_tokens_per_s", "Model"),
    "unscoped_share_pct.lfm2": ("device_trace", "serve_tokens_per_s",
                                "Model"),
}


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


def test_the_manifest_is_clean_and_holds_the_cell_after_the_accepted(real):
    assert manifest.check(real) == []
    names = real.cell_names()
    # the eighth cell, after the accepted seven, of which one takes four chips
    assert names.index(CELL) == 7 == 1 + names.index(
        "longcat-flash-serve-prefill")
    assert [w["name"] for w in real.data["workloads"][:8]
            if w["chips"] == 4] == ["mistral7b-train-4k-fsdp4"]
    entry = next(c for c in real.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    throughput = next(m for m in real.data["end_to_end"]
                      if m["name"] == "serve_tokens_per_s")
    assert CELL in throughput["workloads"] and throughput["bound"] == 0.06


def test_the_cell_reports_throughput_set_up_and_its_fourteen_metrics(real):
    cell = real.cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"]: (m["source"], m["moves"], m["layer"])
            for m in cell.per_layer} == METRICS
    for m in cell.per_layer:
        assert CELL in m["workloads"]
        assert callable(reducers.resolve(m["reducer"]))
    # the cell's own entries stand together, in this order
    own = [name for name in METRICS if name.endswith(".lfm2")]
    names = [m["name"] for m in real.data["per_layer"]]
    first = names.index("fwd_device_ms.lfm2")
    assert len(own) == 11 and names[first:first + len(own)] == own


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_metrics_file_agrees_with_its_entry(real, name):
    entry, = [e for e in real.data["per_layer"] if e["name"] == name]
    with open(os.path.join(real.root, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    for key, value in entry.items():
        # a later cell like one of an entry's cells is appended to its list
        assert spec[key] == (value[:len(spec[key])] if key == "workloads"
                             else value), (name, key)
    assert spec["what"] and ("program_spans:" not in spec["reducer"]
                             or not name.endswith(".lfm2"))
    if name.endswith("_share_pct.lfm2") and entry["layer"] == "Model":
        assert spec["reducer"] == "benchmark.lfm2_counts:scope_share_pct"
        assert spec["params"] == {"scope": name.split("_share")[0]}
    if "roofline" in name or "mfu" in name:
        assert (entry["unit"], entry["better"]) == ("%", "higher")


def test_the_configuration_keeps_every_published_key(real):
    config = real.cell(CELL).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["source"] == ("https://huggingface.co/LiquidAI/"
                                "LFM2-24B-A2B/blob/main/config.json")
    cut = config["reduced"]["serve.1"]
    assert set(cut) == {"num_hidden_layers", "published_layers", "why",
                        "stands_for"}
    assert (cut["num_hidden_layers"], cut["published_layers"]) == (9, KEPT)
    assert "five-stage pipeline" in cut["stands_for"]
    assert "memory_analysis" in cut["why"] and "GB" in cut["why"]
    assert {"tied_head", "head_dim", "conv_width", "conv_split", "router",
            "rotary", "qk_norm"} <= set(config["assumed"])
    assert any("never zeros" in d for d in config["departures"])


def test_dims_are_the_published_sizes_with_the_cells_cut(real):
    cell = real.cell(CELL)
    dims = manifest.model_dims(cell.config, "serve", 1)
    assert dims == {
        "vocab_size": 65536, "d_model": 2048, "n_layers": 9, "n_heads": 32,
        "n_kv_heads": 8, "head_dim": 64, "d_ff": 11776, "rope_theta": 1e6,
        "rms_norm_eps": 1e-5, "conv_width": 3, "num_dense_layers": 2,
        "layer_types": ["conv"] + 2 * PERIOD, "layer_ids": KEPT,
        "n_routed": 64, "n_zero": 0, "top_k": 4, "scale": 1.0,
        "expert_width": 1536, "held": [0, 64]}
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "train", 1)
    # 5.18 B parameters, the issue's arithmetic: a conv + dense layer, two
    # attention + mixture layers, six conv + mixture layers, the embedding
    mixture = 64 * lfm2_counts.expert_params(dims) + 2048 * 64
    conv, attn = (lfm2_counts.shortconv_params(dims),
                  lfm2_counts.attention_params(dims))
    assert (conv, attn, mixture) == (16_777_216, 10_485_760, 604_110_848)
    matmuls = (conv + 3 * 2048 * 11776 + 2 * (attn + mixture)
               + 6 * (conv + mixture) + 65536 * 2048)
    assert matmuls == 5_177_868_288
    # and what init_params makes besides: the taps, the norms, the biases
    rest = 7 * 3 * 2048 + (9 * 2 + 1) * 2048 + 2 * 2 * 64 + 8 * 64
    assert matmuls + rest == 5_177_950_976


def test_init_params_makes_the_count_the_configuration_states(real):
    from ray_tpu.models import transformer
    cell = real.cell(CELL)
    dims = manifest.model_dims(cell.config, "serve", 1)
    cfg = lfm2_decoder.program_config(dims, 8192, cell.deploy["model"])
    tree = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tree)) \
        == 5_177_950_976
    assert "lm_head" not in tree
    assert {k: p["ln1"].shape[0] for k, p in tree["blocks"].items()} == {
        "conv": 1, "attn_moe": 2, "conv_moe": 6}
    assert tree["blocks"]["conv_moe"]["experts"]["wi"].shape == (
        6, 64, 2048, 1536)


def test_program_config_hands_the_program_the_kinds_and_their_sizes():
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel.expert import ExpertConfig
    cfg = lfm2_decoder.program_config(TINY_DIMS, 64, FLOAT32)
    assert cfg == TransformerConfig(
        vocab_size=96, d_model=64, n_layers=9, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=64, dtype=jnp.dtype("float32"), remat=False,
        use_flash=False, rope_theta=1e6, norm_eps=1e-5,
        layer_kinds=("conv", "attn_moe", "conv_moe", "conv_moe", "conv_moe",
                     "attn_moe", "conv_moe", "conv_moe", "conv_moe"),
        layer_ids=tuple(KEPT), qk_norm=True, conv_width=3,
        tie_embeddings=True,
        experts=ExpertConfig(16, 0, 4, 1.0, 32, (0, 16), score="sigmoid",
                             choice_bias=True, normalize=True))


@pytest.mark.parametrize("change,says", [
    ({"conv_bias": True}, "conv_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"model_type": "lfm2"}, "model_type"),
    ({"reduced": {"serve.1": {"num_hidden_layers": 9,
                              "published_layers": [0, 2, 3]}}},
     "rising indices"),
    ({"reduced": {"serve.1": {"num_hidden_layers": 2,
                              "published_layers": [3, 2]}}},
     "rising indices"),
    ({"num_dense_layers": 3,
      "reduced": {"serve.1": {"num_hidden_layers": 2,
                              "published_layers": [0, 2]}}},
     "attention layer with a dense FFN"),
])
def test_a_layer_the_program_does_not_have_is_refused_by_name(change, says):
    with pytest.raises(manifest.ManifestError, match=says):
        lfm2_decoder.dims({**TINY_LFM2, **change}, "serve", 1)


def test_a_program_without_the_mixer_is_refused_before_a_chip(monkeypatch):
    monkeypatch.setattr(lfm2_decoder, "_program_kinds", lambda: None)
    with pytest.raises(manifest.ManifestError,
                       match="no short-convolution mixer"):
        lfm2_decoder.dims(TINY_LFM2, "serve", 1)
    # and by the harness, before any device is looked for
    monkeypatch.setattr(harness, "device_info", lambda: pytest.fail(
        "a device was asked for"))
    with pytest.raises(manifest.ManifestError):
        harness.run_cell(CELL, 1, 1.0, False)


def test_the_traffic_and_the_deployment_are_the_issues(real):
    cell = real.cell(CELL)
    other = real.cell("longcat-flash-serve-prefill")
    assert cell.traffic == other.traffic            # the accepted file
    assert cell.traffic["name"] == "lognormal4k-closed2"
    assert cell.deploy["deployment"] == {
        "max_batch_size": 1, "pad_batch_to": [1],
        "batch_wait_timeout_s": 0.05, "length_buckets": [2048, 4096, 8192],
        "route": "/score", "target_latency_ms": 10000.0}
    assert cell.deploy["reference"] == {
        "prompt_lengths": [1024, 4096, 8192], "prompts_per_length": 1}
    assert cell.deploy["model"] == {"dtype": "bfloat16", "remat": False,
                                    "use_flash": True}
    assert cell.deploy["why"] and cell.deploy["who"]
    why = next(w["why"] for w in real.data["workloads"]
               if w["name"] == CELL)
    assert "57%" in why and "64-wide" in why and "pads routed" in why


# -- the streamed reference draws what init_params draws ---------------------------------


@pytest.mark.parametrize("seed", (SEED, 7, 2**32 + 5))
def test_the_streamed_draw_is_init_params_leaf_for_leaf(seed):
    from ray_tpu.models import transformer
    key = harness.prng_key(seed)
    cfg = lfm2_decoder.program_config(TINY_DIMS, 32, FLOAT32)
    want = jax.jit(lambda k: transformer.init_params(k, cfg))(key)
    got = jax.jit(lambda k: lfm2_reference.draw_tree(k, TINY_DIMS))(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree.leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert a.dtype == b.dtype == jnp.float32, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))
    # the bias is drawn, not zeros, and a cast to bfloat16 leaves it alone
    bias = got["blocks"]["conv_moe"]["router_bias"]
    assert float(jnp.abs(bias).min()) > 0
    np.testing.assert_array_equal(
        bias, bias.astype(jnp.bfloat16).astype(jnp.float32))


def test_the_streamed_logits_are_the_whole_trees():
    key = harness.prng_key(SEED)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0, 96)
    assert lfm2_decoder.reference_params(key, TINY_DIMS, 20) is key
    streamed = jax.jit(lambda k, t: lfm2_decoder.last_logits(
        k, t, TINY_DIMS))(key, tokens)
    whole = lfm2_reference.tree_last_logits(
        lfm2_reference.draw_tree(key, TINY_DIMS), tokens, TINY_DIMS)
    np.testing.assert_allclose(streamed, whole, atol=1e-5)
    assert float(jnp.std(whole)) > 0.05


def test_the_reference_trains_on_the_cpu_under_jax_grad():
    tree = lfm2_reference.draw_tree(harness.prng_key(3), TINY_DIMS)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 13), 0, 96)
    loss, norm = lfm2_reference.loss_and_grad_norm(tree, tokens, TINY_DIMS)
    assert 3.5 < float(loss) < 6.5 and 0 < float(norm) < 100


# -- planted faults read correct: false at the adapter's tolerance ------------------------


def _served(cfg, params, prompts):
    """What the served path replies: the first token and its logit, from
    the program's forward on each prompt padded to 32."""
    from ray_tpu.models import transformer
    replies = []
    for prompt in prompts:
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :len(prompt)] = prompt
        x = transformer.backbone(params, jnp.asarray(tokens), cfg)
        logits = transformer.head(params, x[:, len(prompt) - 1:len(prompt)],
                                  cfg)[0, 0]
        replies.append({"token": int(jnp.argmax(logits)),
                        "logit": float(jnp.max(logits))})
    return replies


# for the planted faults a mixture of few, heavy experts (4, top-2), so that
# one expert is a part of the stream the logits can show. The head is tied
# to an embedding drawn at 0.02, so a logit's spread is 0.02 sqrt(d) = 0.16
# here and the largest of 96 near 0.35. The cell's limit is set by how far
# two correct programs drift apart through eight discrete choices in
# bfloat16 (the adapter's note): scaled to these 64 it is the largest logit
# itself, and it tells unrelated logits, no milder fault. Here both sides
# are float32 and no choice flips, so the comparison reads 1e-5 as built and
# has to read a third of a logit's spread or more under every fault
FAULT_DIMS = lfm2_decoder.dims(
    {**TINY_LFM2, "num_experts": 4, "num_experts_per_tok": 2}, "serve", 1)
FAULT_ATOL = lfm2_decoder.TOLERANCES["logit_atol"] * (64 / 2048) ** 0.5
FAULT_SEEN = 0.05


def _worst(replies, prompts):
    return serve_job._compare(replies, prompts, lfm2_decoder, FAULT_DIMS,
                              SEED, jax.devices()[0])["worst"]


@pytest.fixture(scope="module")
def program():
    from ray_tpu.models import transformer
    cfg = lfm2_decoder.program_config(FAULT_DIMS, 32, FLOAT32)
    params = transformer.init_params(harness.prng_key(SEED), cfg)
    prompts = serve_job._sample_prompts(SEED, [9, 20, 32], 2, 96)
    return cfg, params, prompts


def test_the_program_as_built_is_inside_the_tolerance(program):
    cfg, params, prompts = program
    assert _worst(_served(cfg, params, prompts), prompts) < 1e-4
    assert FAULT_SEEN < FAULT_ATOL == pytest.approx(0.35, abs=0.01)


def _in(params, kind, path, fn):
    """``params`` with the leaf at ``path`` of ``blocks[kind]`` mapped."""
    def walk(tree, keys):
        if not keys:
            return fn(tree)
        return {**tree, keys[0]: walk(tree[keys[0]], keys[1:])}
    return {**params, "blocks": {**params["blocks"],
                                 kind: walk(params["blocks"][kind], path)}}


FAULTS = ("an expert dropped", "the bias left out of the choice",
          "the bias added to the weights", "weights not normalised",
          "softmax for sigmoid", "the taps read forwards",
          "the gates swapped", "a q norm's weight left out",
          "the head untied")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_not_correct(program, fault, monkeypatch):
    from ray_tpu.models import transformer
    from ray_tpu.parallel import expert
    cfg, params, prompts = program
    right_route = expert.route

    def routed(**changes):
        return dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, **changes))

    if fault == "an expert dropped":
        params = _in(params, "conv_moe", ("experts", "wo"),
                     lambda p: p.at[:, 0].set(0.0))
    elif fault == "the bias left out of the choice":
        for kind in ("conv_moe", "attn_moe"):
            params = _in(params, kind, ("router_bias",), jnp.zeros_like)
    elif fault == "the bias added to the weights":
        def route(u, router, c, bias):
            idx, w = right_route(u, router, c, bias)
            return idx, w + 5.0 * bias[idx]
        monkeypatch.setattr(expert, "route", route)
    elif fault == "weights not normalised":
        cfg = routed(normalize=False)
    elif fault == "softmax for sigmoid":
        cfg = routed(score="softmax")
    elif fault == "the taps read forwards":
        params = _in(params, "conv_moe", ("shortconv", "conv"),
                     lambda p: p[..., ::-1])
    elif fault == "the gates swapped":      # [C | B | z] for [B | C | z]
        params = _in(params, "conv_moe", ("shortconv", "w_in"),
                     lambda p: jnp.concatenate(
                         [p[..., 64:128], p[..., :64], p[..., 128:]], -1))
    elif fault == "a q norm's weight left out":
        params = _in(params, "attn_moe", ("attn", "q_norm"),
                     lambda p: 0.25 * p)
    elif fault == "the head untied":
        cfg = dataclasses.replace(cfg, tie_embeddings=False)
        params = {**params, "lm_head": jax.random.normal(
            jax.random.PRNGKey(4), (64, 96)) / 8}
    worst = _worst(_served(cfg, params, prompts), prompts)
    assert worst > FAULT_SEEN, (fault, worst)
    if fault == "the head untied":      # unrelated logits: the cell's limit
        assert worst > FAULT_ATOL, (fault, worst)


# -- counts against a hand count ------------------------------------------------------------


def test_counts_are_the_hand_counts_at_a_small_size():
    dims = TINY_DIMS
    # attention: q and o 64 x 4 x 16, k and v 64 x 2 x 16
    assert lfm2_counts.attention_params(dims) == 2 * 4096 + 2 * 2048
    assert lfm2_counts.shortconv_params(dims) == 64 * 192 + 64 * 64
    assert lfm2_counts.expert_params(dims) == 3 * 64 * 32
    assert lfm2_counts.attention_layers(dims) == 2
    conv = 2 * (16384 + 3 * 64)
    assert lfm2_counts.layer_flops_per_token(0, dims) == (
        conv + 2 * 3 * 64 * 96)
    assert lfm2_counts.layer_flops_per_token(1, dims) == (
        2 * 12288 + 2 * (64 * 16 + 4 * 6144))
    assert lfm2_counts.layer_flops_per_token(2, dims) == (
        conv + 2 * (64 * 16 + 4 * 6144))
    # QK^T and PV over 16, 10 x 11 / 2 pairs, 4 heads, 3 sequences
    assert lfm2_counts.attn_flops(3, 10, dims) == 2 * 2 * 16 * 55 * 4 * 3
    assert lfm2_counts.attn_bytes(3, 10, dims) == 2 * 3 * 10 * (4 + 2) * 16 * 2
    per_token = sum(lfm2_counts.layer_flops_per_token(i, dims)
                    for i in range(9))
    assert lfm2_counts.forward_flops(3, 10, dims) == (
        30 * per_token + 2 * 2 * 2 * 16 * 55 * 4 * 3 + 2 * 3 * 64 * 96)
    assert lfm2_counts.expert_matmul_flops(100, dims) == 2 * 100 * 6144
    assert lfm2_counts.expert_matmul_bytes(100, 5, dims) == (
        2 * (5 * 6144 + 100 * 2 * 64))
    # 3 layer calls of 16 experts in 7 steps: 4 boundaries inside a group
    assert lfm2_counts.expert_reads(3, 7, 16) == 3 * 16 + 4


def test_counts_at_the_published_sizes_are_the_issues(real):
    dims = manifest.model_dims(real.cell(CELL).config, "serve", 1)
    assert lfm2_counts.expert_params(dims) == 9_437_184          # 9.44 M
    experts = 4 * 2 * lfm2_counts.expert_params(dims)            # 75.5 MFLOP
    assert experts == 75_497_472
    conv_layer = lfm2_counts.layer_flops_per_token(2, dims)
    assert conv_layer == pytest.approx(109.3e6, rel=2e-3)
    assert lfm2_counts.layer_flops_per_token(1, dims) == pytest.approx(
        96.7e6, rel=2e-3)
    assert lfm2_counts.layer_flops_per_token(0, dims) == pytest.approx(
        178.3e6, rel=2e-3)
    # at a 4,096-token prompt: 1,061 MFLOP a token, 57% of it in the experts
    per_token = lfm2_counts.forward_flops(1, 4096, dims) / 4096
    assert per_token == pytest.approx(1.061e9, rel=5e-3)
    assert 8 * experts / per_token == pytest.approx(0.57, abs=0.005)
    # 16.8 MFLOP of causal scores a token an attention layer at 4,096
    assert lfm2_counts.attn_flops(1, 4096, dims) / 4096 == pytest.approx(
        16.8e6, rel=2e-3)
    # the attention call is bound by its operations at every bucket
    for seq in (2048, 4096, 8192):
        assert sala_counts.min_seconds(
            lfm2_counts.attn_flops(1, seq, dims),
            lfm2_counts.attn_bytes(1, seq, dims), "TPU v5 lite")[1] == "flops"
    # a step of 1,024 rows over the 2 to 3 experts it touches: the weights'
    # bytes at 128 rows an expert, the operations at 512
    for rows, reads, bound in ((1024, 8, "bytes"), (1024, 3, "flops")):
        assert sala_counts.min_seconds(
            lfm2_counts.expert_matmul_flops(rows, dims),
            lfm2_counts.expert_matmul_bytes(rows, reads, dims),
            "TPU v5 lite")[1] == bound


# -- the readers on a plane this file encodes -----------------------------------------------

PLANE_DIMS = {**TINY_DIMS, "n_layers": 3, "layer_types": ["conv"] + PERIOD[:2],
              "layer_ids": [0, 2, 3]}
CALL = "custom-call"
BODY = "jit(first_token)/while/body/closed_call/"
# one forward of [1, 32] in 1,000 us: a conv + dense layer (shortconv 100,
# mlp 150), an attention + mixture layer (attn 60 + 140 in the kernel, the
# mixture's router 50, its loop's gather 40, three grouped products of 60
# without a tf_op, a scatter-add 30) and a conv + mixture layer of which
# only the mixer (100) is in the window; 50 idle
RECORDS = {
    1: _metadata(1, "%while.1 = (s32[]) while(%tuple.1)"),
    2: _metadata(2, "%fusion.1 = f32[32,192] fusion(%p0)",
                 BODY + "shortconv/bld,de->ble/dot_general:",
                 "convolution fusion"),
    3: _metadata(3, "%fusion.2 = f32[32,96] fusion(%p1)",
                 BODY + "mlp/bld,df->blf/dot_general:", "convolution fusion"),
    4: _metadata(4, "%fusion.3 = f32[32,4,16] fusion(%p2)",
                 BODY + "attn/bld,dhk->blhk/dot_general:",
                 "convolution fusion"),
    5: _metadata(5, "%flash_fwd.4 = f32[4,32,16] custom-call(%fusion.3), "
                 "custom_call_target=\"tpu_custom_call\"",
                 BODY + "attn/core/flash_fwd/pallas_call:", CALL),
    6: _metadata(6, "%fusion.5 = f32[32,16] fusion(%p3)",
                 BODY + "moe/moe/router/td,de->te/dot_general:",
                 "convolution fusion"),
    7: _metadata(7, "%gather.6 = f32[128,64] gather(%p4)",
                 BODY + "moe/moe/experts/while/body/gather:", "gather"),
    8: _metadata(8, "%ragged-dot.7 = f32[128,32] custom-call(%gather.6), "
                 "custom_call_target=\"tpu_custom_call\"", "", CALL),
    9: _metadata(9, "%scatter.8 = f32[32,64] scatter(%p5)",
                 BODY + "moe/moe/experts/while/body/scatter-add:", "scatter"),
    10: _metadata(10, "%ragged-dot-metadata.9 = s32[17] custom-call(%p6), "
                  "custom_call_target=\"tpu_custom_call\"", "", CALL),
}
FORWARD = [_event(1, 0, 1000), _event(2, 0, 100), _event(3, 100, 250),
           _event(4, 250, 310), _event(5, 310, 450), _event(6, 450, 500),
           _event(7, 500, 540), _event(10, 540, 550), _event(8, 550, 610),
           _event(8, 610, 670), _event(8, 670, 730), _event(9, 730, 760),
           _event(2, 760, 860), _event(3, 860, 950)]


def _space():
    host = _plane("/host:CPU", [("main", BASE_NS, [_event(11, 0, 1000)])],
                  {11: _field(1, 11) + _field(2, "bench.window")}, {})
    first = _plane("/device:TPU:0", [("XLA Ops", BASE_NS, FORWARD)], RECORDS)
    return b"".join(_field(1, p) for p in (host, first))


def _span(held, load_max, steps, start_us, layers=1, experts=16):
    at = T0_NS + start_us * 1000
    return Span("moe.route", at, at + 1000, 1,
                {"held": held, "absent": 0, "zero": 0, "load_max": load_max,
                 "layers": layers, "experts": experts, "steps": steps})


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """The encoded run as the harness hands it to the readers."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    path = _written(tmp_path, _space())
    reduced = trace_reduce.load(path, "bench.", "window")
    spans = [_span(128, 12, 2, 460), _span(99, 99, 9, 1001)]
    monkeypatch.setattr(program_spans, "program_spans",
                        lambda ctx: tuple(spans))
    device_scopes._run_leaves.cache_clear()
    device_scopes._run_rows.cache_clear()

    def ctx(dims=PLANE_DIMS, trace=reduced):
        cell = type("C", (), {"name": CELL, "deploy": {}})()
        return reducers.Context(
            cell=cell, trace=trace, device_kind="TPU v5 lite",
            counters={"dims": dims, "serve_tokens_per_s": 20.0,
                      "window_s": 1.0})
    return ctx


def test_the_shares_by_scope_add_to_a_hundred_with_unscoped_among_them(
        traced):
    ctx = traced()
    got = {scope: lfm2_counts.scope_share_pct(ctx, {"scope": scope})
           for scope in ("shortconv", "attn", "mlp", "moe", "unscoped",
                         "head")}
    # 950 us of leaves: the grouped products and their helper name no scope
    assert got["shortconv"] == pytest.approx(100 * 200 / 950)
    assert got["attn"] == pytest.approx(100 * 200 / 950)
    assert got["mlp"] == pytest.approx(100 * 240 / 950)
    assert got["moe"] == pytest.approx(100 * 120 / 950)
    assert got["unscoped"] == pytest.approx(100 * 190 / 950)
    assert got["head"] == 0.0
    assert sum(got.values()) == pytest.approx(100.0)
    assert sum(n.startswith(device_scopes.NOTE_HEAD)
               for n in ctx.notes) == 1 and "shortconv" in ctx.notes[0]
    # the accepted reader, whose scopes are pinned, counts the mixer unscoped
    assert device_scopes.scope_share_pct(traced(), {
        "scope": "unscoped"}) == pytest.approx(100 * 390 / 950)


def test_the_attention_readers_count_the_shapes_the_calls_had(traced):
    ctx = traced()
    # the busy time is the device's: the ``while`` spans the 50 us gap
    assert lfm2_counts.gqa64_attn_share_pct(ctx, {}) == pytest.approx(
        100 * 140 / 1000)
    least = max(lfm2_counts.attn_flops(1, 32, PLANE_DIMS) / 197e12,
                lfm2_counts.attn_bytes(1, 32, PLANE_DIMS) / 819e9)
    got = lfm2_counts.gqa64_attn_roofline_pct(ctx, {})
    assert got == pytest.approx(100 * least / 140e-6) and 0 < got < 100
    # one attention layer in these dims: one call a forward
    flops = lfm2_counts.forward_flops(1, 32, PLANE_DIMS)
    mfu = lfm2_counts.fwd_mfu_pct(ctx, {})
    assert mfu == pytest.approx(100 * flops / (1000e-6 * 197e12))
    assert 0 < mfu < 100
    note, = [n for n in ctx.notes if n.startswith("forward mfu")]
    assert "1.0 forwards of 32 padded tokens (20 real tokens" in note


def test_the_mixtures_readers_count_the_pairs_and_the_steps(traced):
    ctx = traced()
    # the loop: gather 40, the products' helper 10, three products of 60,
    # the scatter-add 30
    assert lfm2_counts.expert_share_pct(ctx, {}) == pytest.approx(
        100 * 260 / 950)
    # 128 pairs in 2 steps of one layer call of 16 experts: 17 experts read;
    # the span past the window's end is not the window's
    least = max(
        lfm2_counts.expert_matmul_flops(128, PLANE_DIMS) / 197e12,
        lfm2_counts.expert_matmul_bytes(128, 17, PLANE_DIMS) / 819e9)
    got = lfm2_counts.expert_matmul_roofline_pct(ctx, {})
    assert got == pytest.approx(100 * least / 180e-6) and 0 < got < 100
    note, = [n for n in ctx.notes if n.startswith("grouped product")]
    assert "128 pairs in 2 steps of 1 layer calls (64 rows a step, " \
        "8.50 experts read a step)" in note
    from benchmark import longcat_counts
    assert longcat_counts.expert_load_max_over_mean(ctx, {}) == (
        pytest.approx(12 * 16 / 128))


READERS = ("fwd_mfu_pct", "gqa64_attn_share_pct", "gqa64_attn_roofline_pct",
           "expert_share_pct", "expert_matmul_roofline_pct")


@pytest.mark.parametrize("reader", READERS + ("scope_share_pct",))
def test_a_reader_that_finds_nothing_returns_nothing(reader, traced,
                                                     monkeypatch):
    read = getattr(lfm2_counts, reader)
    p = {"scope": "shortconv"}
    # another architecture's cell: dims without layer_types
    assert read(traced(dims={"n_heads": 4}), p) is None
    assert read(traced(trace=None), p) is None
    assert read(traced(trace=trace_reduce.Reduced((0, 10), {}, [])),
                p) is None
    # a window that is nobody's trace: no call, no leaf
    off = traced()
    off.trace = trace_reduce.Reduced(
        (off.trace.window[0] + 1, off.trace.window[1]), off.trace.devices, [])
    assert read(off, p) is None
    # spans from before ``steps``: the products' roofline has no count
    if reader == "expert_matmul_roofline_pct":
        old = Span("moe.route", T0_NS + 460_000, T0_NS + 461_000, 1,
                   {"held": 128, "layers": 1, "experts": 16})
        monkeypatch.setattr(program_spans, "program_spans",
                            lambda ctx: (old,))
        assert read(traced(), p) is None


# -- a tiny copy of the cell, through serve.run ---------------------------------------------


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file that
    names the adapter, a closed loop of two callers, a deployment of three
    length buckets at batch 1; new files and entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("lfm2"),
                                    cells=("tiny-serve-closed",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-lfm2.json"), "w") as f:
        json.dump(TINY_LFM2, f)
    with open(os.path.join(base, "traffic", "tiny-closed2.json"), "w") as f:
        json.dump({**benchmark_tiny.TINY_TRAFFIC["tiny-closed"],
                   "name": "tiny-closed2", "clients": 2, "n_lengths": 16,
                   "prompt_len": {"dist": "lognormal", "median": 20,
                                  "sigma": 0.6, "min": 4, "max": 64}}, f)
    deploy = dict(benchmark_tiny.TINY_CELLS["tiny-serve-closed"],
                  name="tiny-lfm2-serve")
    del deploy["traffic"], deploy["like"]
    deploy["deployment"] = {**deploy["deployment"], "max_batch_size": 1,
                            "pad_batch_to": [1],
                            "length_buckets": [16, 32, 64]}
    deploy["reference"] = {"prompt_lengths": [5, 32, 64],
                           "prompts_per_length": 1}
    with open(os.path.join(base, "workloads", "tiny-lfm2-serve.json"),
              "w") as f:
        json.dump(deploy, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-lfm2", "source": "tests only",
        "file": "benchmark/configs/tiny-lfm2.json",
        "reduced": ["num_hidden_layers"], "why": "a toy of the stack"})
    data["workloads"].append({
        "name": "tiny-lfm2-serve", "config": "tiny-lfm2",
        "traffic": "tiny-closed2", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-lfm2-serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_serves_through_the_adapter_and_is_correct(lfm2_root,
                                                               runtime):
    result = harness.run_cell("tiny-lfm2-serve", SEED, 2.0, False,
                              root=lfm2_root, require_tpu=False)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_traced_tiny_cell_reads_the_programs_counters(lfm2_root, runtime):
    """On the CPU there is no device plane: the readers of the device's
    trace return nothing and the line leaves their metrics out; the
    experts' load rides the program's own spans and is read."""
    result = harness.run_cell("tiny-lfm2-serve", SEED, 1.0, True,
                              root=lfm2_root, require_tpu=False)
    assert set(result["metrics"]) == {"serve_startup_s.serve",
                                      "expert_load_max_over_mean.longcat"}
    assert result["metrics"]["expert_load_max_over_mean.longcat"][
        "value"] >= 1.0


def test_a_router_without_its_bias_fails_the_tiny_cell(
        lfm2_root, runtime, monkeypatch, capsys):
    """The comparison that decides ``correct`` tells a program whose choice
    leaves the bias out from the reference (the tiny cell is held to the
    planted faults' limit: both sides are float32 here)."""
    from ray_tpu.parallel import expert
    right = expert.route
    monkeypatch.setattr(
        expert, "route", lambda u, router, cfg, bias: right(
            u, router, cfg, jnp.zeros_like(bias)))
    monkeypatch.setitem(lfm2_decoder.TOLERANCES, "logit_atol", FAULT_SEEN)
    result = harness.run_cell("tiny-lfm2-serve", SEED, 1.0, False,
                              root=lfm2_root, require_tpu=False)
    assert not result["correct"]
    assert "logits off the reference by" in capsys.readouterr().out
