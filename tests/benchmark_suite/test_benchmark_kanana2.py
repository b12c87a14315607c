"""Kanana-2's configuration (``kanana-2-30b-a3b``), adapter, reference, counts,
readers and cell: the manifest takes the cell and its files name what exists,
the published row is whole, the adapter has every name and refuses a program
without the latent mixer, the reference draws nothing from ``ray_tpu``, the
counts agree with a count made from the reference's own shapes, a tiny copy
of the cell trains through ``JaxTrainer.fit`` on the CPU (correct, and
incorrect with a part of the mathematics left out of the reference), and the
readers on synthetic operations."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny
from benchmark import (device_scopes, harness, kanana2_counts,
                       kanana2_reference, manifest, reducers)
from benchmark.adapters import kanana2_decoder
from test_benchmark_manifest import ROOTS, cell_order_faults, real_root

REPO = benchmark_tiny.REPO
SEED = 2**31 + 48
CELL, CONFIG, TRAFFIC = ("kanana2-train-8k", "kanana-2-30b-a3b",
                         "packed-8k-1seq")
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601"
          "/blob/main/config.json")
# the catalog row's config (architectures.jsonl), by hand
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256,
}
TINY = {
    **PUBLISHED, "name": "tiny-kanana2", "source": "tests only",
    "adapter": "benchmark.adapters.kanana2_decoder",
    "hidden_size": 32, "intermediate_size": 48, "kv_lora_rank": 16,
    "moe_intermediate_size": 24, "n_routed_experts": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 6, "qk_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 256, "rope_theta": 10000.0,
    "assumed": {"bias_update_rate": 0.001, "lr_warmup_steps": 20},
    "reduced": {"train.1": {"num_hidden_layers": 3,
                            "published_layers": [0, 1, 2],
                            "n_routed_experts": 4, "first_expert": 4,
                            "vocab_size": 128, "why": "tests"}},
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


def tiny_dims():
    return kanana2_decoder.dims(TINY, "train", 1)


# -- the manifest with the cell ---------------------------------------------


def test_the_manifest_takes_the_cell_and_every_guard_holds(real):
    assert manifest.check(real) == []
    assert cell_order_faults(real.data["workloads"]) == []
    entry, = [w for w in real.data["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, TRAFFIC, 1)
    rate, = [m for m in real.data["end_to_end"]
             if m["name"] == "train_tokens_per_s"]
    assert CELL in rate["workloads"]
    assert entry["chips"] == 1      # the four-chip quota stays with one used


def test_the_cells_files_name_what_exists(real):
    cell = real.cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    # PR 60: the nine entries that repeated a training cell's reader are
    # that reader's first entry now (``.train``), the embedding's share
    # (0.44% of the step) is retired: eight of the seventeen are its own
    mine = [m for m in cell.per_layer if m["name"].endswith(".kanana2")]
    shared = [m for m in cell.per_layer if m["name"].endswith(".train")]
    assert len(mine) == 8 and len(shared) == 9
    assert len(cell.per_layer) == 17
    for m in cell.per_layer:
        assert (m["workloads"] == [CELL]) == (m in mine)
        assert CELL in m["workloads"]
        assert m["moves"] == ("setup_s" if m["name"].startswith("fit_")
                              else "train_tokens_per_s")
        assert callable(reducers.resolve(m["reducer"]))
    shares = {m["params"]["scope"] for m in cell.per_layer
              if m["reducer"] == "benchmark.device_scopes:scope_share_pct"}
    # every top-level scope the step enters but the embedding's, and the rest
    assert shares == {"attn", "mlp", "moe", "head", "optimizer", "unscoped"}
    assert cell.traffic == {**cell.traffic, "kind": "token_batches",
                            "seq_len": 8192, "sequences_per_step": 1,
                            "dataset_rows": 32}
    assert cell.deploy["model"] == {"dtype": "bfloat16", "remat": True,
                                    "use_flash": True}
    assert cell.deploy["reference"] == {"sequences": 4, "seq_len": 512}


def test_the_configuration_keeps_every_published_key(real):
    entry = next(c for c in real.data["configs"] if c["name"] == CONFIG)
    with open(os.path.join(real.root, entry["file"])) as f:
        config = json.load(f)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["source"] == entry["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert list(config["reduced"]) == ["train.1"]
    cut = config["reduced"]["train.1"]
    assert set(cut) == {"num_hidden_layers", "published_layers",
                        "n_routed_experts", "first_expert", "vocab_size",
                        "why", "stands_for"}
    # the floors: the leading dense layer and at least four after it, at
    # least 8 routed experts, at least an eighth of the vocabulary
    assert cut["published_layers"] == list(range(cut["num_hidden_layers"]))
    assert cut["num_hidden_layers"] in (5, 6)       # the issue's rule
    assert (cut["n_routed_experts"], cut["first_expert"]) == (16, 0)
    assert cut["vocab_size"] * 8 == config["vocab_size"]
    assert config["assumed"]["bias_update_rate"] == 0.001
    assert config["assumed"]["lr_warmup_steps"] == 2000
    assert "8 chips" in cut["stands_for"]
    assert config["departures"] and config["published"]


def test_dims_are_the_published_sizes_with_the_cells_cut(real):
    cell = real.cell(CELL)
    assert manifest.adapter(cell.config) is kanana2_decoder
    dims = manifest.model_dims(cell.config, "train", 1)
    depth = cell.config["reduced"]["train.1"]["num_hidden_layers"]
    assert dims == {
        "vocab_size": 16032, "d_model": 2048, "n_layers": depth,
        "layer_ids": list(range(depth)), "n_heads": 32, "nope_dim": 128,
        "rope_dim": 64, "v_dim": 128, "kv_rank": 512, "d_ff": 6144,
        "rope_theta": 1e6, "rms_norm_eps": 1e-6, "first_k_dense": 1,
        "n_routed": 128, "top_k": 6, "scale": 2.448, "expert_width": 768,
        "shared_width": 1536, "held": [0, 16], "bias_rate": 0.001,
        "warmup_steps": 2000}
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "serve", 1)


def test_the_adapter_has_every_name_and_refuses_what_it_does_not_build(
        monkeypatch):
    for name in manifest.ADAPTER_NAMES:
        assert hasattr(kanana2_decoder, name), name
    assert set(kanana2_decoder.TOLERANCES) == set(manifest.TOLERANCE_KEYS)
    with pytest.raises(manifest.ManifestError, match="q_lora_rank"):
        kanana2_decoder.dims({**TINY, "q_lora_rank": 1536}, "train", 1)
    with pytest.raises(manifest.ManifestError, match="bias_update_rate"):
        kanana2_decoder.dims({**TINY, "assumed": {"bias_update_rate": 0.01}},
                             "train", 1)
    with pytest.raises(manifest.ManifestError, match="published_layers"):
        kanana2_decoder.dims({**TINY, "reduced": {"train.1": {
            "num_hidden_layers": 2, "published_layers": [0, 1, 2]}}},
            "train", 1)
    # a program from before the latent mixer (the parent commit): refused
    # at once, before a chip is taken
    monkeypatch.setattr(kanana2_decoder, "_program_kinds", lambda: None)
    with pytest.raises(manifest.ManifestError, match="no latent mixer"):
        kanana2_decoder.dims(TINY, "train", 1)


def test_program_config_hands_the_program_the_layer():
    from ray_tpu.models import transformer
    cfg = kanana2_decoder.program_config(tiny_dims(), 32,
                                         {"dtype": "float32"})
    assert cfg.layer_kinds == (transformer.LATENT, transformer.LATENT_MOE,
                               transformer.LATENT_MOE)
    assert cfg.latent == transformer.LatentConfig(None, 16, 16, 8, 16)
    e = cfg.experts
    assert (e.n_routed, e.n_zero, e.top_k, e.scale, e.width, e.held) == (
        16, 0, 3, 2.448, 24, (4, 4))
    assert (e.score, e.choice_bias, e.normalize, e.shared_width) == (
        "sigmoid", True, True, 48)
    assert (cfg.vocab_size, cfg.d_ff, cfg.norm_eps, cfg.warmup_steps) == (
        128, 48, 1e-6, 20)


def test_the_reference_draws_nothing_from_the_program():
    path = os.path.join(REPO, "benchmark", "kanana2_reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "math", "typing", "jax"}


# -- the counts --------------------------------------------------------------


def test_flops_a_token_agree_with_a_count_from_the_references_shapes():
    """Every matmul the reference's forward makes of the program's tree,
    counted from the leaves' own shapes (2 x a weight's size a token; an
    expert's at the pairs a token sends it) and the scores' from their
    [heads, S, S] shape, lower triangle."""
    from ray_tpu.models import transformer
    dims, seq = tiny_dims(), 32
    cfg = kanana2_decoder.program_config(dims, seq, {"dtype": "float32"})
    params = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg))
    pairs = 0.4             # held pairs a token and layer, as if measured

    def size(p):
        return int(np.prod(p.shape))

    total = 2 * size(params["lm_head"])
    for i in range(dims["n_layers"]):
        kind = (kanana2_reference.MOE_KIND
                if kanana2_reference.is_moe(i, dims)
                else kanana2_reference.DENSE_KIND)
        layer = jax.tree.map(        # one layer's leaves of the stacked tree
            lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
            params["blocks"][kind])
        total += 2 * sum(size(p) for name, p in layer["latent"].items()
                         if name != "kv_norm")
        # [heads, S, S] scores at 24 and probabilities times v at 16
        total += 2 * (24 + 16) * dims["n_heads"] * (seq * (seq + 1) // 2) / seq
        if kanana2_reference.is_moe(i, dims):
            total += 2 * (size(layer["router"])
                          + sum(size(p) for p in layer["shared"].values()))
            total += 2 * pairs * sum(
                size(p) // dims["held"][1] for p in layer["experts"].values())
        else:
            total += 2 * sum(size(p) for p in layer["mlp"].values())
    assert kanana2_counts.forward_flops_per_token(seq, dims, pairs) \
        == pytest.approx(total, rel=1e-12)
    assert kanana2_counts.train_flops_per_token(seq, dims, pairs) \
        == pytest.approx(3 * total, rel=1e-12)


def test_the_published_layer_is_the_issues_163_mflop_a_token(real):
    dims = manifest.model_dims(real.cell(CELL).config, "train", 1)
    mixer = (2 * kanana2_counts.mla_params(dims)
             + kanana2_counts.attn_flops_per_token(8192, dims))
    assert mixer / 1e6 == pytest.approx(136.6, abs=0.1)
    assert 6 * 2048 * dims["shared_width"] / 1e6 == pytest.approx(18.9,
                                                                  abs=0.1)
    assert kanana2_counts.expected_pairs_per_token(dims) == 0.75
    assert kanana2_counts.layer_flops_per_token(1, 8192, dims) / 1e6 \
        == pytest.approx(163.1, abs=0.1)
    # a held expert's rows a step: an eighth of a deployment's 3,072
    assert 8192 * 0.75 / 16 == 384


def test_the_kernels_calls_count_each_product_at_its_own_width():
    dims = {"nope_dim": 128, "rope_dim": 64, "v_dim": 128, "n_heads": 32}
    pairs = 8192 * 8193 // 2
    per = 2 * pairs * 32
    assert kanana2_counts.flash_call_flops("flash_fwd", 1, 8192, dims) \
        == per * (192 + 128)
    assert kanana2_counts.flash_call_flops("flash_dq", 1, 8192, dims) \
        == per * (2 * 192 + 128)
    assert kanana2_counts.flash_call_flops("flash_dkv", 1, 8192, dims) \
        == per * (2 * 192 + 2 * 128)
    rows = 8192 * 32
    assert kanana2_counts.flash_call_bytes("flash_fwd", 1, 8192, dims) \
        == rows * (2 * (2 * 192 + 2 * 128) + 4)
    assert kanana2_counts.flash_call_bytes("flash_dkv", 1, 8192, dims) \
        == rows * (2 * (3 * 192 + 4 * 128) + 8)


def _op(name, start, end, tf_op=""):
    return device_scopes.Op(start, end, device_scopes.OpRecord(
        name, tf_op, "", 0, 0, ""))


def test_the_kernel_reader_tells_the_three_calls_apart():
    from benchmark import program_spans
    kernel = f'custom-call(...), custom_call_target="{program_spans.KERNEL_CATEGORY}"'
    ops = [_op(f"%flash_fwd.3 = bf16[32,8192,128] {kernel}", 0, 10),
           _op(f"%flash_dq.1 = bf16[32,8192,192] {kernel}", 10, 30),
           _op(f"%flash_dkv.1 = (bf16[32,8192,192]) {kernel}", 30, 70),
           _op("%fusion.flash_fwd_like = bf16[8] fusion(...)", 70, 80)]
    assert [len(kanana2_counts._kernel_calls(ops, call)) for call in
            ("flash_fwd", "flash_dq", "flash_dkv")] == [1, 1, 1]


def test_the_readers_return_nothing_for_another_architectures_cell():
    ctx = reducers.Context(cell=None, trace=None,
                           counters={"dims": tiny_dims()}, device_kind="cpu")
    for reader in (kanana2_counts.step_mfu_pct,
                   kanana2_counts.mla_fwd_roofline_pct,
                   kanana2_counts.mla_bwd_roofline_pct,
                   kanana2_counts.expert_share_pct,
                   kanana2_counts.expert_matmul_roofline_pct):
        assert reader(ctx, {}) is None
    assert kanana2_counts.inner_scope_share_pct(
        ctx, {"inside": "attn", "scope": "core"}) is None


# -- the reference against the program, float32 on the CPU --------------------


def test_the_adapters_last_logits_are_the_programs():
    from ray_tpu.models import transformer
    dims = tiny_dims()
    cfg = kanana2_decoder.program_config(dims, 24, {"dtype": "float32",
                                                     "use_flash": False})
    key = jax.random.PRNGKey(7)
    params = kanana2_decoder.reference_params(key, dims, 24)
    assert jax.tree.structure(params) == jax.tree.structure(
        transformer.init_params(key, cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 24), 0, 128)
    want = kanana2_decoder.last_logits(params, tokens, dims)
    got = transformer.apply(params, tokens, cfg)[:, -1]
    assert want.shape == (2, 128)
    # float32 on both sides, no choice flips: rounding alone
    np.testing.assert_allclose(got, want,
                               atol=kanana2_decoder.TOLERANCES["logit_atol"]
                               * 1e-3)


# -- a tiny copy of the cell, through JaxTrainer.fit -----------------------------


@pytest.fixture(scope="module")
def kanana2_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file that
    names the adapter, a workload file, entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("kanana2"),
                                    cells=("tiny-train",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-kanana2.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-kanana2", "source": "tests only",
        "file": "benchmark/configs/tiny-kanana2.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
        "why": "a toy of Kanana-2's decoder"})
    deploy = dict(benchmark_tiny.TINY_CELLS["tiny-train"],
                  name="tiny-kanana2-train")
    del deploy["traffic"], deploy["like"]
    with open(os.path.join(base, "workloads", "tiny-kanana2-train.json"),
              "w") as f:
        json.dump(deploy, f)
    data["workloads"].append({
        "name": "tiny-kanana2-train", "config": "tiny-kanana2",
        "traffic": "tiny-batches", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-kanana2-train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_trains_through_fit_and_is_correct(kanana2_root, runtime):
    result = harness.run_cell("tiny-kanana2-train", SEED, 1.0, False,
                              root=kanana2_root, require_tpu=False)
    assert result["correct"] and result["failed"] == 0, result
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] >= 1


def test_a_traced_tiny_cell_leaves_out_what_it_cannot_read(kanana2_root,
                                                           runtime):
    result = harness.run_cell("tiny-kanana2-train", SEED, 1.0, True,
                              root=kanana2_root, require_tpu=False)
    # no device plane on the CPU: the trace's readers return nothing; the
    # experts' load is read off the host's ``moe.route`` spans, which the
    # step's ``moe_load`` feeds without a call-back
    assert set(result["metrics"]) == {"fit_startup_s.train",
                                      "data_wait_ms.train",
                                      "expert_load_max_over_mean.kanana2"}
    assert 1.0 <= result["metrics"]["expert_load_max_over_mean.kanana2"][
        "value"] <= 4.0


@pytest.mark.parametrize("wrong", ["no_shared_experts", "one_expert_fewer",
                                   "no_scaling_factor"])
def test_a_part_left_out_of_the_reference_makes_the_cell_incorrect(
        kanana2_root, runtime, monkeypatch, wrong):
    sound = kanana2_decoder.loss_and_grad_norm

    def reference(params, tokens, dims):
        if wrong == "no_shared_experts":
            blocks = dict(params["blocks"])
            moe = dict(blocks[kanana2_reference.MOE_KIND])
            moe["shared"] = jax.tree.map(jnp.zeros_like, moe["shared"])
            blocks[kanana2_reference.MOE_KIND] = moe
            params = {**params, "blocks": blocks}
        elif wrong == "one_expert_fewer":
            first, count = dims["held"]
            dims = {**dims, "held": [first + 1, count - 1]}
            blocks = dict(params["blocks"])
            moe = dict(blocks[kanana2_reference.MOE_KIND])
            moe["experts"] = jax.tree.map(lambda p: p[:, 1:], moe["experts"])
            blocks[kanana2_reference.MOE_KIND] = moe
            params = {**params, "blocks": blocks}
        else:
            dims = {**dims, "scale": 1.0}
        return sound(params, tokens, dims)

    monkeypatch.setattr(kanana2_decoder, "loss_and_grad_norm", reference)
    result = harness.run_cell("tiny-kanana2-train", SEED, 1.0, False,
                              root=kanana2_root, require_tpu=False)
    assert not result["correct"] and result["failed"] == 0
