"""LongCat-Flash's cell (``longcat-flash-serve-prefill``): its files, its
adapter and streamed reference, its counts against hand counts, its readers
on synthetic planes, planted faults that the adapter's tolerance must
refuse, and a tiny copy of the cell through ``serve.run``: at toy sizes on
the CPU."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
from benchmark import (harness, longcat_counts, longcat_reference, manifest,
                       program_spans, reducers, sala_counts, serve_job)
from benchmark.adapters import longcat_decoder
from benchmark.program_spans import Span
from benchmark.trace_reduce import DeviceTrace, Event, Reduced
from test_benchmark_manifest import ROOTS, real_root

CELL = "longcat-flash-serve-prefill"
CONFIG = "longcat-flash-omni"
SEED = 2**31 + 41
US = 1000
# the catalog row's ``config``, key for key
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
# d 64, 4 heads of 16 + 8 / 16, ranks 32 / 16, 16 routed + 8 zero experts of
# which the first 8 are held, top-4, 2 of 3 layers, half the vocabulary
TINY_LONGCAT = {
    **PUBLISHED, "name": "tiny-longcat", "source": "tests only",
    "adapter": "benchmark.adapters.longcat_decoder",
    "vocab_size": 192, "hidden_size": 64, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "num_layers": 3, "num_attention_heads": 4,
    "kv_lora_rank": 16, "q_lora_rank": 32, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "n_routed_experts": 16,
    "zero_expert_num": 8, "moe_topk": 4,
    "reduced": {"serve.1": {"num_layers": 2, "n_routed_experts": 8,
                            "first_expert": 0, "vocab_size": 96,
                            "why": "tests"}},
}
TINY_DIMS = longcat_decoder.dims(TINY_LONGCAT, "serve", 1)
FLOAT32 = {"dtype": "float32", "use_flash": False, "remat": False}


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
    assert names.index(CELL) > names.index("minicpm-sala-serve-longdoc")
    entry = next(c for c in real.data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    throughput = next(m for m in real.data["end_to_end"]
                      if m["name"] == "serve_tokens_per_s")
    assert CELL in throughput["workloads"] and throughput["bound"] == 0.06


def test_the_cell_reports_throughput_set_up_and_its_nine_metrics(real):
    cell = real.cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert {m["name"]: (m["source"], m["moves"], m["layer"])
            for m in cell.per_layer} == {
        # PR 60: the offline cell's entries hold the forward's time and
        # the batch's host share (one reader, one entry), the serving
        # cells' start-up stands for the retired constructor's gauge
        "fwd_device_ms.offline": ("device_trace", "serve_tokens_per_s",
                                  "Model"),
        "fwd_mfu_pct.longcat": ("device_trace", "serve_tokens_per_s",
                                "Model"),
        "mla_attn_share_pct.longcat": ("device_trace", "serve_tokens_per_s",
                                       "Kernel"),
        "mla_attn_roofline_pct.longcat": ("device_trace",
                                          "serve_tokens_per_s", "Kernel"),
        "expert_share_pct.longcat": ("device_trace", "serve_tokens_per_s",
                                     "Expert layer"),
        "expert_matmul_roofline_pct.longcat": (
            "device_trace", "serve_tokens_per_s", "Expert layer"),
        "expert_load_max_over_mean.longcat": (
            "program_counter", "serve_tokens_per_s", "Expert layer"),
        "idle_batch_host_pct.offline": ("program_span", "serve_tokens_per_s",
                                        "Serve: replica batcher"),
        "serve_startup_s.serve": ("host_clock", "setup_s",
                                  "Entry: serve API"),
    }
    for m in cell.per_layer:
        assert m["workloads"] == [CELL] or CELL in m["workloads"]
        assert callable(reducers.resolve(m["reducer"]))


def test_the_configuration_keeps_every_published_key(real):
    config = real.cell(CELL).config
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    cut = config["reduced"]["serve.1"]
    assert (cut["num_layers"], cut["n_routed_experts"], cut["first_expert"],
            cut["vocab_size"]) == (4, 16, 0, 16384)
    assert "32 chips" in cut["stands_for"] and cut["why"]
    assert {"router", "zero_experts", "rotary", "softmax_scale",
            "mla_norms"} <= set(config["assumed"])
    assert config["departures"]


def test_dims_are_the_published_sizes_with_the_cells_cut(real):
    cell = real.cell(CELL)
    dims = manifest.model_dims(cell.config, "serve", 1)
    assert dims == {
        "vocab_size": 16384, "d_model": 6144, "n_layers": 4, "n_heads": 64,
        "d_ff": 12288, "rope_theta": 1e7, "rms_norm_eps": 1e-5,
        "q_rank": 1536, "kv_rank": 512, "nope_dim": 128, "rope_dim": 64,
        "v_dim": 128, "n_routed": 512, "n_zero": 256, "top_k": 12,
        "scale": 6.0, "expert_width": 2048, "held": [0, 16]}
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "train", 1)
    # 5.17 B parameters: 4 x (639 M + 16 x 37.75 M) + 2 x 100.7 M
    layer = (2 * longcat_counts.mla_params(dims) + 2 * 3 * 6144 * 12288
             + 6144 * 768 + 16 * longcat_counts.expert_params(dims))
    assert 4 * layer + 2 * 16384 * 6144 == 5_172_625_408


def test_program_config_hands_the_program_the_kind_and_its_sizes():
    from ray_tpu.models.transformer import LatentConfig, TransformerConfig
    from ray_tpu.parallel.expert import ExpertConfig
    cfg = longcat_decoder.program_config(TINY_DIMS, 64, FLOAT32)
    assert cfg == TransformerConfig(
        vocab_size=96, d_model=64, n_layers=2, n_heads=4, d_ff=96,
        max_seq_len=64, dtype=jnp.dtype("float32"), remat=False,
        use_flash=False, rope_theta=1e7, norm_eps=1e-5,
        layer_kinds=("shortcut", "shortcut"),
        latent=LatentConfig(32, 16, 16, 8, 16),
        experts=ExpertConfig(16, 8, 4, 6.0, 32, (0, 8)))


@pytest.mark.parametrize("change,says", [
    ({"attention_method": "MHA"}, "attention_method"),
    ({"zero_expert_type": "copy"}, "zero_expert_type"),
    ({"mla_scale_kv_lora": False}, "mla_scale_kv_lora"),
    ({"reduced": {"serve.1": {"n_routed_experts": 8, "first_expert": 12}}},
     "not among the published"),
])
def test_a_layer_the_program_does_not_have_is_refused_by_name(change, says):
    with pytest.raises(manifest.ManifestError, match=says):
        longcat_decoder.dims({**TINY_LONGCAT, **change}, "serve", 1)


def test_a_program_without_the_layer_kind_is_refused_before_a_chip(
        monkeypatch):
    monkeypatch.setattr(longcat_decoder, "_program_has_the_layer",
                        lambda: False)
    with pytest.raises(manifest.ManifestError, match="shortcut"):
        longcat_decoder.dims(TINY_LONGCAT, "serve", 1)


def test_the_traffic_and_the_deployment_are_the_issues(real):
    cell = real.cell(CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("requests",
                                                          "closed", 2)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.6, "min": 1024, "max": 8192}
    assert (mix["preroll_s"], mix["timeout_s"], mix["pattern_seed"]) == (
        5.0, 60.0, 41)
    assert mix["n_lengths"] % 2 == 0 and 40 <= mix["n_lengths"] <= 200
    assert cell.deploy["deployment"] == {
        "max_batch_size": 2, "pad_batch_to": [1, 2],
        "batch_wait_timeout_s": 0.05, "length_buckets": [2048, 4096, 8192],
        "route": "/score", "target_latency_ms": 10000.0}
    assert cell.deploy["reference"] == {
        "prompt_lengths": [1024, 4096, 8192], "prompts_per_length": 1}
    assert cell.deploy["model"] == {"dtype": "bfloat16", "remat": False,
                                    "use_flash": True}
    assert cell.deploy["why"] and cell.deploy["who"] and mix["why"]


# -- the streamed reference draws what init_params draws ---------------------------------


@pytest.mark.parametrize("seed", (SEED, 7, 2**32 + 5))
def test_the_streamed_draw_is_init_params_leaf_for_leaf(seed):
    from ray_tpu.models import transformer
    key = harness.prng_key(seed)
    cfg = longcat_decoder.program_config(TINY_DIMS, 32, FLOAT32)
    want = jax.jit(lambda k: transformer.init_params(k, cfg))(key)
    got = jax.jit(lambda k: longcat_reference.draw_tree(k, TINY_DIMS))(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree.leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert a.dtype == b.dtype == jnp.float32, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))


def test_the_streamed_logits_are_the_whole_trees():
    key = harness.prng_key(SEED)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0, 96)
    assert longcat_decoder.reference_params(key, TINY_DIMS, 20) is key
    streamed = jax.jit(lambda k, t: longcat_decoder.last_logits(
        k, t, TINY_DIMS))(key, tokens)
    whole = longcat_reference.tree_last_logits(
        longcat_reference.draw_tree(key, TINY_DIMS), tokens, TINY_DIMS)
    np.testing.assert_allclose(streamed, whole, atol=1e-5)


def test_the_reference_trains_on_the_cpu_under_jax_grad():
    tree = longcat_reference.draw_tree(harness.prng_key(3), TINY_DIMS)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 13), 0, 96)
    loss, norm = longcat_reference.loss_and_grad_norm(tree, tokens, TINY_DIMS)
    assert 3.5 < float(loss) < 6.5 and 0 < float(norm) < 100


# -- planted faults read correct: false at the adapter's tolerance ------------------------


def _served(cfg, params, prompts, patch=None):
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


# for the planted faults a mixture of few, heavy experts (4 routed, all
# held, and 2 zero-compute ones, top-2), so that one expert is a part of the
# stream the logits can show: at the published 12 of 768 one held expert's
# weighted part is about 6/768 of an expert's output
FAULT_DIMS = longcat_decoder.dims({
    **TINY_LONGCAT, "n_routed_experts": 4, "zero_expert_num": 2,
    "moe_topk": 2, "reduced": {"serve.1": {
        "num_layers": 2, "n_routed_experts": 4, "vocab_size": 96}}},
    "serve", 1)


def _worst(replies, prompts):
    return serve_job._compare(replies, prompts, longcat_decoder, FAULT_DIMS,
                              SEED, jax.devices()[0])["worst"]


@pytest.fixture(scope="module")
def program():
    from ray_tpu.models import transformer
    cfg = longcat_decoder.program_config(FAULT_DIMS, 32, FLOAT32)
    params = transformer.init_params(harness.prng_key(SEED), cfg)
    prompts = serve_job._sample_prompts(SEED, [9, 20, 32], 2, 96)
    return cfg, params, prompts


def test_the_program_as_built_is_inside_the_tolerance(program):
    cfg, params, prompts = program
    assert _worst(_served(cfg, params, prompts), prompts) < 1e-3


def _scaled(params, leaf, factor):
    blocks = params["blocks"]["shortcut"]
    return {**params, "blocks": {"shortcut": {
        **blocks, "attn": {**blocks["attn"],
                           leaf: blocks["attn"][leaf] * factor}}}}


FAULTS = ("a held expert dropped", "zero-compute experts return 0",
          "weights renormalised", "the factor 6 left out",
          "q's scaling left out", "the latent's scaling left out",
          "k_r rotated another way", "a norm's weight left out")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_not_correct(program, fault, monkeypatch):
    from ray_tpu.models import transformer
    from ray_tpu.parallel import expert
    cfg, params, prompts = program
    right_route = expert.route
    if fault == "a held expert dropped":
        blocks = params["blocks"]["shortcut"]
        params = {**params, "blocks": {"shortcut": {
            **blocks, "experts": {**blocks["experts"], "wo": blocks[
                "experts"]["wo"].at[:, 0].set(0.0)}}}}
    elif fault == "zero-compute experts return 0":
        def route(u, router, c):
            idx, w = right_route(u, router, c)
            return idx, jnp.where(idx >= c.n_routed, 0.0, w)
        monkeypatch.setattr(expert, "route", route)
    elif fault == "weights renormalised":
        def route(u, router, c):
            idx, w = right_route(u, router, c)
            return idx, w / jnp.sum(w, -1, keepdims=True)
        monkeypatch.setattr(expert, "route", route)
    elif fault == "the factor 6 left out":
        cfg = dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, scale=1.0))
    elif fault == "q's scaling left out":
        params = _scaled(params, "wq_b", (32 / 64) ** 0.5)
    elif fault == "the latent's scaling left out":
        params = _scaled(params, "wkv_b", (16 / 64) ** 0.5)
    elif fault == "k_r rotated another way":
        right_rope = transformer._rope_interleaved

        def rope(x, theta, positions):
            # the shared head's pairs taken as (i, i + half), not (2i, 2i+1)
            if x.shape[2] == 1:
                return transformer._rope(x, theta, positions)
            return right_rope(x, theta, positions)
        monkeypatch.setattr(transformer, "_rope_interleaved", rope)
    elif fault == "a norm's weight left out":
        params = _scaled(params, "kv_norm", 0.5)
    worst = _worst(_served(cfg, params, prompts), prompts)
    assert worst > longcat_decoder.TOLERANCES["logit_atol"], (fault, worst)


# -- counts against a hand count ------------------------------------------------------------


def test_counts_are_the_hand_counts_at_a_small_size():
    dims = TINY_DIMS
    # one MLA block: 64x32 + 32x4x24 + 64x24 + 16x4x32 + 4x16x64
    assert longcat_counts.mla_params(dims) == 2048 + 3072 + 1536 + 2048 + 4096
    assert longcat_counts.expert_params(dims) == 3 * 64 * 32
    # 4 picks over 24 outputs of which 8 are held here
    assert longcat_counts.expected_pairs_per_token(dims) == pytest.approx(
        4 * 8 / 24)
    per_token = 2 * (2 * 12800 + 2 * 3 * 64 * 96 + 64 * 24
                     + (4 * 8 / 24) * 6144)
    assert longcat_counts.layer_matmul_flops_per_token(dims) == (
        pytest.approx(per_token))
    # attention: QK^T over 24 and PV over 16, 10 x 11 / 2 pairs, 4 heads
    assert longcat_counts.mla_attn_flops(3, 10, dims) == (
        2 * (24 + 16) * 55 * 4 * 3)
    assert longcat_counts.mla_attn_bytes(3, 10, dims) == (
        3 * 10 * 4 * (24 + 24 + 16 + 16) * 2)
    assert longcat_counts.forward_flops(3, 10, dims) == pytest.approx(
        2 * (30 * per_token + 2 * 2 * 40 * 55 * 4 * 3) + 2 * 3 * 64 * 96)
    assert longcat_counts.expert_matmul_flops(100, dims) == 2 * 100 * 6144
    assert longcat_counts.expert_matmul_bytes(100, 5, dims) == (
        2 * (5 * 8 * 6144 + 100 * 2 * 64))


def test_counts_at_the_published_sizes_are_the_issues(real):
    dims = manifest.model_dims(real.cell(CELL).config, "serve", 1)
    assert longcat_counts.mla_params(dims) == 90_570_752       # 90.6 M
    assert longcat_counts.expert_params(dims) == 37_748_736    # 37.75 M
    assert longcat_counts.expected_pairs_per_token(dims) == 0.25
    # 1,268 MFLOP of projections and FFNs, 9.4 of router, 19 of experts
    per_token = longcat_counts.layer_matmul_flops_per_token(dims)
    assert per_token == pytest.approx(1.2965e9, rel=1e-3)
    # 41 KFLOP x L of attention a token a call, two calls a layer
    assert longcat_counts.mla_attn_flops(1, 8192, dims) / 8192 == (
        pytest.approx(2 * 320 * 64 * 8193 / 2))
    # at 8,192 tokens a call is bound by its operations, not its bytes
    assert sala_counts.min_seconds(
        longcat_counts.mla_attn_flops(1, 8192, dims),
        longcat_counts.mla_attn_bytes(1, 8192, dims),
        "TPU v5 lite")[1] == "flops"
    # the grouped product of an expected call is bound by its weights' bytes
    assert sala_counts.min_seconds(
        longcat_counts.expert_matmul_flops(1024, dims),
        longcat_counts.expert_matmul_bytes(1024, 1, dims),
        "TPU v5 lite")[1] == "bytes"


# -- the readers on synthetic planes --------------------------------------------------------

CALL = "custom-call tpu_custom_call"
PLANE_DIMS = {**TINY_DIMS, "n_heads": 4, "n_layers": 1}


def _op(name, category, shape, start_us, end_us):
    return (name, category, shape, start_us * US, end_us * US)


def _span(held, load_max, start_us, layers=1, experts=8):
    return Span("moe.route", start_us * US, (start_us + 1) * US, 1,
                {"held": held, "absent": 10, "zero": 5, "load_max": load_max,
                 "layers": layers, "experts": experts})


class _Cell:
    deploy = {"deployment": {"pad_batch_to": [1, 2],
                             "length_buckets": [16, 32]}}


def _window(monkeypatch, ops, spans=(), dims=PLANE_DIMS):
    """A window of 10,000 us in which the device is busy 6,000."""
    monkeypatch.setattr(program_spans, "find_trace", lambda window: "a.pb")
    monkeypatch.setattr(longcat_counts, "_device_ops",
                        lambda path: tuple(ops))
    monkeypatch.setattr(program_spans, "program_spans",
                        lambda ctx: tuple(spans))
    busy = [Event("fusion.1", 1000 * US, 7000 * US, "fusion")]
    reduced = Reduced((0, 10000 * US), {0: DeviceTrace(busy, [])}, [])
    return reducers.Context(cell=_Cell(), trace=reduced,
                            counters={"dims": dims},
                            device_kind="TPU v5 lite")


OPS = [
    _op("while.1", "while", (), 900, 7000),             # the layers' loop
    # one forward of [1, 32]: two attention calls of 4 heads
    _op("flash_fwd.2", CALL, (4, 32, 16), 1000, 1300),
    _op("flash_fwd.3", CALL, (4, 32, 16), 4000, 4200),
    # the mixture: router (width 24), the choice (a sort, last dim 4), the
    # running count of the 8 held experts' tokens, the dropless loop and
    # what runs inside it
    _op("fusion.5", "fusion", (32, 24), 1400, 1500),
    _op("fusion.6", "fusion", (32, 4), 1500, 1550),
    _op("sort.7", "sort", (32, 24), 1550, 1600),
    _op("fusion.8", "fusion", (8, 32), 1600, 1620),
    _op("while.9", "while", (), 1700, 2400),
    _op("gather.10", "gather", (16, 64), 1700, 1800),
    _op("ragged-dot-none.11", CALL, (16, 32), 1800, 2000),
    _op("ragged-dot-metadata.12", CALL, (9,), 1790, 1800),
    _op("scatter.13", "scatter", (32, 64), 2300, 2400),
    # not the mixture's: an FFN product, and a call after the window
    _op("fusion.20", "fusion", (32, 96), 2500, 3500),
    _op("flash_fwd.2", CALL, (4, 32, 16), 9900, 10100),
]


def test_the_shares_are_their_operations_over_the_busy_time(monkeypatch):
    ctx = _window(monkeypatch, OPS)
    assert longcat_counts.mla_attn_share_pct(ctx, {}) == pytest.approx(
        100 * 500 / 6000)
    # 100 + 50 + 50 + 20 outside the loop, 100 + 200 + 100 inside it (the
    # metadata call overlaps the gather: a union, not a sum)
    assert longcat_counts.expert_share_pct(ctx, {}) == pytest.approx(
        100 * (220 + 400) / 6000)


def test_the_forwards_mfu_counts_the_shapes_the_calls_had(monkeypatch):
    ctx = _window(monkeypatch, OPS)
    flops = longcat_counts.forward_flops(1, 32, PLANE_DIMS)
    assert longcat_counts.fwd_mfu_pct(ctx, {}) == pytest.approx(
        100 * flops / (6000e-6 * 197e12))
    assert any("1.0 forwards" in n for n in ctx.notes)


def test_the_roofline_shares_are_least_time_over_device_time(monkeypatch):
    spans = [_span(40, 9, 1650), _span(24, 7, 5000), _span(99, 99, 10001)]
    ctx = _window(monkeypatch, OPS, spans)
    least = 2 * max(longcat_counts.mla_attn_flops(1, 32, PLANE_DIMS) / 197e12,
                    longcat_counts.mla_attn_bytes(1, 32, PLANE_DIMS) / 819e9)
    assert longcat_counts.mla_attn_roofline_pct(ctx, {}) == pytest.approx(
        100 * least / 500e-6)
    # 64 pairs in 2 layer calls, against the one product's 200 us (the
    # metadata call is not the product)
    least = max(longcat_counts.expert_matmul_flops(64, PLANE_DIMS) / 197e12,
                longcat_counts.expert_matmul_bytes(64, 2, PLANE_DIMS) / 819e9)
    got = longcat_counts.expert_matmul_roofline_pct(ctx, {})
    assert got == pytest.approx(100 * least / 200e-6)
    assert 0 < got <= 100
    # (9 + 7) x 8 experts over 64 pairs held; the span past the window's
    # end is not the window's
    assert longcat_counts.expert_load_max_over_mean(ctx, {}) == (
        pytest.approx(16 * 8 / 64))


@pytest.mark.parametrize("reader", [
    "fwd_mfu_pct", "mla_attn_share_pct", "mla_attn_roofline_pct",
    "expert_share_pct", "expert_matmul_roofline_pct",
    "expert_load_max_over_mean"])
def test_a_reader_that_finds_nothing_returns_nothing(reader, monkeypatch):
    read = getattr(longcat_counts, reader)
    other = [_op("fusion.20", "fusion", (32, 96), 2500, 3500)]
    assert read(_window(monkeypatch, other), {}) is None
    # another architecture's cell: dims without a held share
    assert read(_window(monkeypatch, OPS, dims={"n_heads": 4}), {}) is None
    none = reducers.Context(cell=_Cell(), trace=None,
                            counters={"dims": PLANE_DIMS}, device_kind="cpu")
    assert read(none, {}) is None


# -- a tiny copy of the cell, through serve.run ---------------------------------------------


@pytest.fixture(scope="module")
def longcat_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file that
    names the adapter, a closed loop of two callers, a deployment of three
    length buckets; new files and entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("longcat"),
                                    cells=("tiny-serve-closed",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-longcat.json"), "w") as f:
        json.dump(TINY_LONGCAT, f)
    with open(os.path.join(base, "traffic", "tiny-closed2.json"), "w") as f:
        json.dump({**benchmark_tiny.TINY_TRAFFIC["tiny-closed"],
                   "name": "tiny-closed2", "clients": 2, "n_lengths": 16,
                   "prompt_len": {"dist": "lognormal", "median": 20,
                                  "sigma": 0.6, "min": 4, "max": 64}}, f)
    deploy = dict(benchmark_tiny.TINY_CELLS["tiny-serve-closed"],
                  name="tiny-longcat-serve")
    del deploy["traffic"], deploy["like"]
    deploy["deployment"] = {**deploy["deployment"], "max_batch_size": 2,
                            "pad_batch_to": [1, 2],
                            "length_buckets": [16, 32, 64]}
    deploy["reference"] = {"prompt_lengths": [5, 32, 64],
                           "prompts_per_length": 1}
    with open(os.path.join(base, "workloads", "tiny-longcat-serve.json"),
              "w") as f:
        json.dump(deploy, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-longcat", "source": "tests only",
        "file": "benchmark/configs/tiny-longcat.json",
        "reduced": ["num_layers", "n_routed_experts", "vocab_size"],
        "why": "a toy shortcut stack"})
    data["workloads"].append({
        "name": "tiny-longcat-serve", "config": "tiny-longcat",
        "traffic": "tiny-closed2", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-longcat-serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_serves_through_the_adapter_and_is_correct(longcat_root,
                                                               runtime):
    result = harness.run_cell("tiny-longcat-serve", SEED, 2.0, False,
                              root=longcat_root, require_tpu=False)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_traced_tiny_cell_reads_the_programs_counters(longcat_root,
                                                        runtime):
    """On the CPU there is no device plane: the readers of the device's
    trace return nothing and the line leaves their metrics out; the
    expert load rides the program's own spans and is read."""
    result = harness.run_cell("tiny-longcat-serve", SEED, 1.0, True,
                              root=longcat_root, require_tpu=False)
    assert not set(result["metrics"]) & {
        "fwd_device_ms.offline", "fwd_mfu_pct.longcat",
        "mla_attn_share_pct.longcat", "mla_attn_roofline_pct.longcat",
        "expert_share_pct.longcat", "expert_matmul_roofline_pct.longcat"}
    assert "serve_startup_s.serve" in result["metrics"]
    assert result["metrics"]["expert_load_max_over_mean.longcat"][
        "value"] >= 1.0


def test_a_mixture_without_its_factor_fails_the_tiny_cell(
        longcat_root, runtime, monkeypatch, capsys):
    """The comparison that decides ``correct`` tells a program whose
    router's weights lack ``routed_scaling_factor`` from the reference."""
    from ray_tpu.parallel import expert
    right = expert.route

    def unscaled(u, router, cfg):
        idx, w = right(u, router, cfg)
        return idx, w / cfg.scale

    monkeypatch.setattr(expert, "route", unscaled)
    result = harness.run_cell("tiny-longcat-serve", SEED, 1.0, False,
                              root=longcat_root, require_tpu=False)
    assert not result["correct"]
    assert "logits off the reference by" in capsys.readouterr().out
