"""MiniCPM-SALA's configuration, adapter, counts, readers and cell: the
published row kept, the cell's files as ISSUE 37 names them, the counts
against hand counts, the readers on synthetic planes, and a tiny copy of the
cell through ``serve.run`` on the CPU (correct, and incorrect with a wrong
reference or a fault planted in the sparse operation), and the adapter's
check of the two operations against the reference's functions of the same
q, k and v. Nothing here pins the number of cells or the last cell: every
guard of the real manifest runs on both roots."""

import dataclasses
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import pytest

import benchmark_tiny
from benchmark import (harness, manifest, program_spans, reducers,
                       sala_counts, sala_reference)
from benchmark.adapters import sala_decoder
from benchmark.trace_reduce import DeviceTrace, Event, Reduced
from test_benchmark_manifest import ROOTS, cell_order_faults, real_root

REPO = benchmark_tiny.REPO
SEED = 2**31 + 37
CELL, CONFIG, MIX = ("minicpm-sala-serve-longdoc", "minicpm-sala",
                     "lognormal12k-closed2")
LIGHTNING, MINICPM4 = "lightning-attn", "minicpm4"
# the catalog row's config (architectures.jsonl, MiniCPM-SALA), by hand
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": (
        [MINICPM4] + [LIGHTNING] * 8 + [MINICPM4] + [LIGHTNING] * 6
        + [MINICPM4] * 2 + [LIGHTNING] * 4 + [MINICPM4] + [LIGHTNING] * 6
        + [MINICPM4] * 3),
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-6, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
}
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "init_blocks": 1, "window_size": 2048,
          "dense_len": 8192}
# PR 60: the forward's time, the batch's host share and the start-up are the
# first entries of their readers (the offline cell's, the serving cells');
# the constructor's gauge is retired for the start-up it read
METRICS = [
    "serve_startup_s.serve", "fwd_device_ms.offline",
    "idle_batch_host_pct.offline",
    "proxy_self_ms.longdoc", "linear_attn_share_pct.longdoc",
    "sparse_attn_share_pct.longdoc", "linear_attn_roofline_pct.longdoc",
    "sparse_attn_roofline_pct.longdoc"]
TINY_SPARSE = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
               "topk": 4, "init_blocks": 1, "window_size": 16,
               "dense_len": 32}
TINY_SALA = {
    **{k: v for k, v in PUBLISHED.items()
       if isinstance(v, (bool, str)) or k in ("scale_emb", "scale_depth",
                                              "rms_norm_eps", "rope_theta")},
    "name": "tiny-sala", "source": "tests only",
    "adapter": "benchmark.adapters.sala_decoder",
    "head_dim": 16, "hidden_size": 64, "intermediate_size": 96,
    "lightning_head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
    "mixer_types": [LIGHTNING, MINICPM4, LIGHTNING, LIGHTNING, LIGHTNING,
                    MINICPM4],
    "num_attention_heads": 4, "num_hidden_layers": 6,
    "num_key_value_heads": 2, "vocab_size": 128, "dim_model_base": 16,
    "assumed": {"sparse_config": TINY_SPARSE},
    "reduced": {"serve.1": {"num_hidden_layers": 4, "first_layer": 1,
                            "why": "tests"}},
}
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


# -- the manifest with the cell ---------------------------------------------------


def test_the_manifest_holds_the_cell_and_every_guard_holds(real):
    assert manifest.check(real) == []
    assert cell_order_faults(real.data["workloads"]) == []
    entry, = [w for w in real.data["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, MIX, 1)
    throughput, = [m for m in real.data["end_to_end"]
                   if m["name"] == "serve_tokens_per_s"]
    assert throughput["workloads"][:2] == ["internlm2-serve-offline", CELL]
    assert throughput["bound"] == 0.06


def test_the_cell_reports_throughput_set_up_and_its_eight_metrics(real):
    cell = real.cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == METRICS
    for m in cell.per_layer:
        assert (m["workloads"] == [CELL]) == m["name"].endswith(".longdoc")
        assert CELL in m["workloads"]
        assert m["moves"] == ("setup_s" if m["name"].startswith(
            "serve_startup") else "serve_tokens_per_s")
        assert callable(reducers.resolve(m["reducer"])) and m["what"]
        # the cell's own readers live in this configuration's own module
        assert not m["name"].endswith(".longdoc") or m[
            "reducer"].startswith("benchmark.sala_counts:")
    by_name = {m["name"]: m for m in cell.per_layer}
    assert by_name["fwd_device_ms.offline"]["params"] == {
        "program": "jit_first_token", "stat": "mean"}
    assert by_name["idle_batch_host_pct.offline"]["params"] == {
        "class": "batch_execute"}
    assert by_name["proxy_self_ms.longdoc"]["params"] == {
        "span": "serve.request", "less": "serve.await_replica"}
    assert by_name["serve_startup_s.serve"]["params"] == {
        "key": "serve_startup_s"}
    assert sala_counts.span_self_ms is program_spans.span_self_ms
    for share in ("linear_attn_roofline_pct.longdoc",
                  "sparse_attn_roofline_pct.longdoc"):
        assert (by_name[share]["unit"], by_name[share]["better"]) == (
            "%", "higher")


def test_the_configuration_keeps_every_published_key(real):
    entry, = [c for c in real.data["configs"] if c["name"] == CONFIG]
    with open(os.path.join(real.root, entry["file"])) as f:
        config = json.load(f)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert list(config["reduced"]) == ["serve.1"]
    cut = config["reduced"]["serve.1"]
    assert set(cut) == {"num_hidden_layers", "first_layer", "why",
                        "stands_for"}
    # ISSUE 37's rule: published layers 9 to 16, or 0 to 3
    assert (cut["first_layer"], cut["num_hidden_layers"]) in ((9, 8), (0, 4))
    assert config["assumed"]["sparse_config"] == SPARSE
    for key in ("lightning_decay", "lightning_layer", "sparse_layer",
                "residual", "padding", "not_used_by_the_forward"):
        assert config["assumed"][key]
    assert config["departures"]


def test_dims_are_the_published_sizes_with_the_cells_cut(real):
    cell = real.cell(CELL)
    assert manifest.adapter(cell.config) is sala_decoder
    dims = manifest.model_dims(cell.config, "serve", 1)
    cut = cell.config["reduced"]["serve.1"]
    first, layers = cut["first_layer"], cut["num_hidden_layers"]
    kept = PUBLISHED["mixer_types"][first:first + layers]
    assert dims == {
        "vocab_size": 73448, "d_model": 4096, "n_layers": layers,
        "n_heads": 32, "n_kv_heads": 2, "head_dim": 128, "d_ff": 16384,
        "rope_theta": 1e4, "rms_norm_eps": 1e-6, "mixer_types": kept,
        "layer_ids": list(range(first, first + layers)),
        "published_layers": 32, "scale_emb": 12.0, "scale_depth": 1.4,
        "dim_model_base": 256, "sparse_config": SPARSE}
    # whole periods in the published ratio of 1 : 3
    assert kept.count(LIGHTNING) == 3 * kept.count(MINICPM4)
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "train", 1)


def test_program_config_hands_the_program_the_kinds_and_the_scalings():
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.ops.sparse_attention import SparseConfig
    dims = sala_decoder.dims(TINY_SALA, "serve", 1)
    assert dims["mixer_types"] == [MINICPM4] + [LIGHTNING] * 3
    assert dims["layer_ids"] == [1, 2, 3, 4]
    cfg = sala_decoder.program_config(dims, 64, {"dtype": "float32",
                                                 "use_flash": False})
    assert cfg == TransformerConfig(
        vocab_size=128, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=96, max_seq_len=64, dtype=jnp.dtype("float32"), remat=True,
        use_flash=False, rope_theta=1e4, norm_eps=1e-6,
        layer_kinds=("sparse", "linear", "linear", "linear"),
        layer_ids=(1, 2, 3, 4), decay_depth=6, embed_scale=12.0,
        residual_scale=1.4 / math.sqrt(6), logit_scale=16 / 64,
        sparse=SparseConfig(**TINY_SPARSE))


@pytest.mark.parametrize("change,says", [
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"use_output_norm": False}, "use_output_norm"),
    ({"lightning_scale": "1"}, "lightning_scale"),
    ({"lightning_nkv": 2}, "linear layers' heads"),
    ({"mixer_types": [LIGHTNING, "window", LIGHTNING, LIGHTNING, LIGHTNING,
                      MINICPM4]}, "mixer_types"),
])
def test_a_layer_the_program_does_not_have_is_refused_by_name(change, says):
    with pytest.raises(manifest.ManifestError, match=says):
        sala_decoder.dims({**TINY_SALA, **change}, "serve", 1)


def test_the_traffic_and_the_deployment_are_the_issues(real):
    cell = real.cell(CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["loop"], mix["clients"]) == ("requests",
                                                          "closed", 2)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.6, "min": 4096, "max": 32768}
    assert (mix["preroll_s"], mix["timeout_s"], mix["pattern_seed"]) == (
        5.0, 60.0, 37)
    assert mix["n_lengths"] % 2 == 0 and 20 <= mix["n_lengths"] <= 80
    assert mix["why"] and cell.deploy["why"] and cell.deploy["who"]
    assert cell.deploy["job"] == "serve"
    assert cell.deploy["deployment"] == {
        "max_batch_size": 2, "pad_batch_to": [1, 2],
        "batch_wait_timeout_s": 0.05,
        "length_buckets": [8192, 16384, 24576, 32768], "route": "/score",
        "target_latency_ms": 10000.0}
    assert cell.deploy["reference"] == {
        "prompt_lengths": [4096, 12288, 32768], "prompts_per_length": 1}
    assert cell.deploy["model"] == {"dtype": "bfloat16", "remat": False,
                                    "use_flash": True}
    # the first bucket is dense_len itself: a prompt is served by the branch
    # that its real length takes in the reference
    assert cell.deploy["deployment"]["length_buckets"][0] == SPARSE[
        "dense_len"]


# -- counts against hand counts -----------------------------------------------------


def test_linear_attention_counts_are_the_recurrences():
    # 2 sequences of 512 tokens, 4 heads of 128: k^T v and q S, each 128 x
    # 128 multiply-adds a token and head
    assert sala_counts.linear_attn_flops(2, 512, 4, 128) == (
        2 * 512 * 4 * (2 * 128 * 128 + 2 * 128 * 128))
    # q, k, v, o in bfloat16
    assert sala_counts.linear_attn_bytes(2, 512, 4, 128) == (
        4 * 2 * 512 * 4 * 128 * 2)
    s, bound = sala_counts.min_seconds(
        sala_counts.linear_attn_flops(1, 32768, 32, 128),
        sala_counts.linear_attn_bytes(1, 32768, 32, 128), "TPU v5 lite")
    assert bound == "bytes"
    assert s == pytest.approx(4 * 32768 * 4096 * 2 / 819e9)


def test_sparse_attention_counts_follow_what_a_query_may_select():
    tiny = {**TINY_SPARSE, "topk": 2}          # at most 2 blocks of 8 tokens
    # 24 queries: 1 + 2 + ... + 16 for the first sixteen, then 16 each
    assert sala_counts.selectable_tokens(24, tiny) == 136 + 8 * 16
    # pooled windows [2 j, 2 j + 4) at or before t: none for t < 3, then
    # (t - 3) // 2 + 1
    assert sala_counts.pooled_windows(8, tiny) == 1 + 1 + 2 + 2 + 3
    assert sala_counts.pooled_windows(3, tiny) == 0
    # QK^T and PV over the selectable tokens, one product over the windows
    assert sala_counts.sparse_attn_flops(3, 24, 4, 16, tiny) == (
        2 * 3 * 4 * 16 * (2 * 264 + sala_counts.pooled_windows(24, tiny)))
    # q and o at 4 heads, k and v at 2
    assert sala_counts.sparse_attn_bytes(3, 24, 4, 2, 16) == (
        2 * 3 * 24 * (4 + 2) * 16 * 2)
    # at the published constants a 32,768-token sequence attends 4,096
    # tokens a query from the 4,096th on, and is bound by its operations
    assert sala_counts.selectable_tokens(32768, SPARSE) == (
        4096 * 4097 // 2 + (32768 - 4096) * 4096)
    _, bound = sala_counts.min_seconds(
        sala_counts.sparse_attn_flops(1, 32768, 32, 128, SPARSE),
        sala_counts.sparse_attn_bytes(1, 32768, 32, 2, 128), "TPU v5 lite")
    assert bound == "flops"


# -- the readers on synthetic planes ----------------------------------------------------

DIMS = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 128,
        "mixer_types": [MINICPM4, LIGHTNING], "sparse_config": SPARSE}
CALL = "custom-call tpu_custom_call"


def _op(name, category, shape, start_us, end_us):
    return (name, category, shape, start_us * US, end_us * US)


def _window(monkeypatch, ops, dims=DIMS):
    """A window of 10,000 us in which the device is busy 6,000."""
    monkeypatch.setattr(program_spans, "find_trace", lambda window: "a.pb")
    monkeypatch.setattr(sala_counts, "_device_ops", lambda path: tuple(ops))
    busy = [Event("fusion.1", 1000 * US, 7000 * US, "fusion")]
    reduced = Reduced((0, 10000 * US), {0: DeviceTrace(busy, [])}, [])
    return reducers.Context(cell=None, trace=reduced,
                            counters={"dims": dims},
                            device_kind="TPU v5 lite")


OPS = [
    # two linear calls of one sequence of 16,384 tokens, 4 heads
    _op("linear_attn_fwd.1", CALL, (4, 16384, 128), 1000, 1200),
    _op("linear_attn_fwd.1", CALL, (4, 16384, 128), 1300, 1600),
    # one sparse operation on 2 sequences of 16,384: K/V heads 2, group 2,
    # query tiles of 512 stacked to 1,024 rows
    _op("sparse_attn_scores.1", CALL, (4, 16384, 1024), 2000, 2500),
    _op("sort.3", "sort", (2, 2, 16384, 256), 2500, 2700),
    _op("sparse_attn_fwd.1", CALL, (4, 32, 1024, 128), 3000, 4300),
    # not ours: another kernel, a fusion that borrows the name, a call
    # after the window
    _op("flash_fwd.6", CALL, (32, 8192, 128), 4400, 4500),
    _op("linear_attn_fwd_fusion.2", "fusion", (4, 16384, 128), 4500, 4600),
    _op("linear_attn_fwd.1", CALL, (4, 16384, 128), 9900, 10100),
]


def test_the_shares_are_the_named_calls_over_the_busy_time(monkeypatch):
    ctx = _window(monkeypatch, OPS)
    assert sala_counts.linear_attn_share_pct(ctx, {}) == pytest.approx(
        100 * 500 / 6000)
    assert sala_counts.sparse_attn_share_pct(ctx, {}) == pytest.approx(
        100 * (500 + 200 + 1300) / 6000)


def test_the_roofline_shares_count_the_calls_own_lengths(monkeypatch):
    ctx = _window(monkeypatch, OPS)
    # bytes bound: q, k, v, o of 16,384 x 4 x 128 bfloat16, twice
    least = 2 * 4 * 16384 * 4 * 128 * 2 / 819e9
    assert sala_counts.linear_attn_roofline_pct(ctx, {}) == pytest.approx(
        100 * least / 500e-6)
    flops = sala_counts.sparse_attn_flops(2, 16384, 4, 128, SPARSE)
    nbytes = sala_counts.sparse_attn_bytes(2, 16384, 4, 2, 128)
    least = max(flops / 197e12, nbytes / 819e9)
    got = sala_counts.sparse_attn_roofline_pct(ctx, {})
    assert got == pytest.approx(100 * least / 2000e-6)
    assert 0 < got <= 100
    assert any("sparse attention roofline: 1 operations" in n
               for n in ctx.notes)


@pytest.mark.parametrize("reader", [
    "linear_attn_share_pct", "sparse_attn_share_pct",
    "linear_attn_roofline_pct", "sparse_attn_roofline_pct"])
def test_a_reader_that_finds_no_call_returns_nothing(reader, monkeypatch):
    read = getattr(sala_counts, reader)
    # the parent commit's program, or another cell: kernels of other names
    ctx = _window(monkeypatch, [op for op in OPS if op[0].startswith(
        ("flash", "linear_attn_fwd_fusion"))])
    assert read(ctx, {}) is None
    # another architecture's dims
    ctx = _window(monkeypatch, OPS, dims={"n_heads": 4})
    assert read(ctx, {}) is None
    # no device plane (the CPU tests' runs), no trace at all
    ctx = _window(monkeypatch, OPS)
    ctx.trace = Reduced((0, 100 * US), {}, [])
    assert read(ctx, {}) is None
    ctx.trace = None
    assert read(ctx, {}) is None
    # a run whose trace file is gone
    ctx = _window(monkeypatch, OPS)
    monkeypatch.setattr(program_spans, "find_trace", lambda window: None)
    assert read(ctx, {}) is None


# -- the two operations against the reference's functions of the same q, k, v ------------


# 16 blocks of 8 tokens at 128 tokens, of which a query keeps 6: the first,
# the two or three of its window, and two or three by their scores
CHECK_DIMS = {**sala_decoder.dims(TINY_SALA, "serve", 1),
              "sparse_config": {**TINY_SPARSE, "topk": 6}}


def _op(name):
    """The operation's module: the package re-exports the function under
    the module's own name."""
    return importlib.import_module(f"ray_tpu.ops.{name}")


def _selecting(change):
    right = _op("sparse_attention").selected_blocks
    return lambda probs, cfg: right(probs, dataclasses.replace(cfg, **change))


def _plant(monkeypatch, fault):
    linear_attention, sparse_attention = (_op("linear_attention"),
                                          _op("sparse_attention"))
    if fault == "decay":
        right = linear_attention.decay_rates
        monkeypatch.setattr(linear_attention, "decay_rates",
                            lambda h, layer, depth: right(h, layer + 1, depth))
    elif fault == "zeroed":
        right = sparse_attention._attend_by_kernel
        monkeypatch.setattr(sparse_attention, "_attend_by_kernel",
                            lambda *a: jnp.zeros_like(right(*a)))
    elif fault is not None:
        monkeypatch.setattr(sparse_attention, "selected_blocks", _selecting({
            "window": {"window_size": 1}, "topk": {"topk": 3},
            "first": {"init_blocks": 0}}[fault]))


@pytest.mark.parametrize("fault,off", [
    (None, ()), ("window", ("sparse",)), ("topk", ("sparse",)),
    ("first", ("sparse",)), ("zeroed", ("sparse",)), ("decay", ("linear",))])
def test_the_operations_check_tells_a_fault_in_an_operation(monkeypatch,
                                                           fault, off):
    """The interpreted kernels against the reference's functions of the same
    bfloat16 q, k and v: no row off as built; with the window, the count of
    blocks, the first block, the output or the decay wrong, most rows."""
    _plant(monkeypatch, fault)
    sparse, linear = jax.jit(
        lambda key: sala_decoder.operations_rows_off(key, 128, CHECK_DIMS))(
            jax.random.PRNGKey(SEED % 1000))
    limit = sala_decoder.OPERATIONS["rows_off_max"]
    assert (float(sparse) > 5 * limit) == ("sparse" in off), float(sparse)
    assert (float(linear) > 5 * limit) == ("linear" in off), float(linear)
    if not off:
        assert float(sparse) == float(linear) == 0.0


def test_the_sparse_operation_is_not_checked_in_the_dense_branch():
    sparse, linear = sala_decoder.operations_rows_off(
        jax.random.PRNGKey(1), TINY_SPARSE["dense_len"], CHECK_DIMS)
    assert float(sparse) == 0.0 and float(linear) == 0.0


# -- a tiny copy of the cell, through serve.run -------------------------------------------


@pytest.fixture(scope="module")
def sala_root(tmp_path_factory):
    """The cell as this PR adds it, at a toy size: a configuration file
    that names the adapter, a closed loop of two callers, a deployment whose
    first length bucket is the tiny ``dense_len``; new files and entries."""
    root = benchmark_tiny.make_root(tmp_path_factory.mktemp("sala"),
                                    cells=("tiny-serve-closed",))
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "tiny-sala.json"), "w") as f:
        json.dump(TINY_SALA, f)
    with open(os.path.join(base, "traffic", "tiny-closed2.json"), "w") as f:
        json.dump({**benchmark_tiny.TINY_TRAFFIC["tiny-closed"],
                   "name": "tiny-closed2", "clients": 2, "n_lengths": 16,
                   "prompt_len": {"dist": "lognormal", "median": 40,
                                  "sigma": 0.6, "min": 8, "max": 96}}, f)
    deploy = dict(benchmark_tiny.TINY_CELLS["tiny-serve-closed"],
                  name="tiny-sala-serve")
    del deploy["traffic"], deploy["like"]
    deploy["deployment"] = {**deploy["deployment"], "max_batch_size": 2,
                            "pad_batch_to": [1, 2],
                            "length_buckets": [32, 64, 96]}
    deploy["reference"] = {"prompt_lengths": [20, 48, 96],
                           "prompts_per_length": 1}
    with open(os.path.join(base, "workloads", "tiny-sala-serve.json"),
              "w") as f:
        json.dump(deploy, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "tiny-sala", "source": "tests only",
        "file": "benchmark/configs/tiny-sala.json",
        "reduced": ["num_hidden_layers"], "why": "a toy mixed stack"})
    data["workloads"].append({
        "name": "tiny-sala-serve", "config": "tiny-sala",
        "traffic": "tiny-closed2", "chips": 1, "why": "a toy of the cell"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-sala-serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    assert manifest.check(manifest.Manifest(root)) == []
    return root


def test_a_tiny_cell_serves_both_branches_and_is_correct(sala_root, runtime):
    result = harness.run_cell("tiny-sala-serve", SEED, 2.0, False,
                              root=sala_root, require_tpu=False)
    assert result["correct"], result
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_traced_tiny_cell_leaves_out_what_it_cannot_read(sala_root,
                                                           runtime):
    """On the CPU there is no device plane: the readers of the device's
    trace return nothing and the line leaves their metrics out."""
    result = harness.run_cell("tiny-sala-serve", SEED, 1.0, True,
                              root=sala_root, require_tpu=False)
    assert not set(result["metrics"]) & {
        "fwd_device_ms.offline", "linear_attn_share_pct.longdoc",
        "sparse_attn_share_pct.longdoc", "linear_attn_roofline_pct.longdoc",
        "sparse_attn_roofline_pct.longdoc"}
    assert "serve_startup_s.serve" in result["metrics"]
    assert "proxy_self_ms.longdoc" in result["metrics"]


def test_a_reference_with_another_window_fails_the_tiny_cell(
        sala_root, runtime, monkeypatch):
    """The comparison that decides ``correct`` tells a selection with half
    the window from the one the program runs."""
    right = sala_reference.last_logits

    def wrong(params, tokens, dims):
        return right(params, tokens, {**dims, "sparse_config": {
            **dims["sparse_config"], "window_size": 8}})

    monkeypatch.setattr(sala_decoder, "last_logits", wrong)
    monkeypatch.setitem(sala_decoder.TOLERANCES, "logit_atol", 1e-4)
    result = harness.run_cell("tiny-sala-serve", SEED, 1.0, False,
                              root=sala_root, require_tpu=False)
    assert not result["correct"]


def test_a_fault_in_the_sparse_operation_fails_the_tiny_cell(
        sala_root, runtime, monkeypatch, capsys):
    """The operations' check reaches ``correct`` through the one number the
    harness compares: the logit's error reads ``OPERATIONS_OFF`` and more."""
    _plant(monkeypatch, "window")
    result = harness.run_cell("tiny-sala-serve", SEED, 1.0, False,
                              root=sala_root, require_tpu=False)
    assert not result["correct"]
    assert "logits off the reference by 1." in capsys.readouterr().out
