"""``BENCHMARK.json`` and the data files it names are well formed, and a
later PR can add a cell and a per-layer metric without editing a file."""

import json
import os

import pytest

import benchmark_tiny
from benchmark import manifest, peaks, reducers

REPO = benchmark_tiny.REPO
CELLS = ["mistral7b-train-4k", "internlm2-serve-steady",
         "mistral7b-train-4k-fsdp4", "internlm2-serve-offline"]
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps")
PUBLISHED = {
    "mistral-7b-v0.3": (4096, 14336, 32, 8, 32768, 1e6, 1e-5, 32),
    "internlm2-1.8b": (2048, 8192, 16, 8, 92544, 1e6, 1e-5, 24),
}


@pytest.fixture(scope="module")
def real():
    return manifest.Manifest(REPO)


def test_manifest_passes_every_check_of_form(real):
    assert manifest.check(real) == []


def test_manifest_has_exactly_the_contracts_keys(real):
    assert set(real.data) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert real.data["paths"] == ["benchmark", "tests/benchmark_suite"]
    assert len(json.dumps(real.data)) < 64 * 1024


def test_cells_are_the_issues_and_in_its_order(real):
    names = real.cell_names()
    assert names == [c for c in CELLS if c in names] and names
    assert names[0] == "mistral7b-train-4k"
    four = [w["name"] for w in real.data["workloads"] if w["chips"] == 4]
    assert four in ([], ["mistral7b-train-4k-fsdp4"])


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_configuration_keeps_every_published_width(real, name):
    entry = next(c for c in real.data["configs"] if c["name"] == name)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    *widths, layers = PUBLISHED[name]
    assert [config[k] for k in WIDTHS] == widths
    assert config["num_hidden_layers"] == layers
    assert config["source"] == entry["source"]
    for cut in config["reduced"].values():
        assert set(cut) <= {"num_hidden_layers", "why", "stands_for"}
    cuts_depth = any("num_hidden_layers" in c
                     for c in config["reduced"].values())
    assert entry["reduced"] == (["num_hidden_layers"] if cuts_depth else [])


def test_every_metric_has_units_and_a_bound_in_range(real):
    for m in real.data["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] == "host_clock"
    for m in real.data["end_to_end"] + real.data["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]) and " " not in m["unit"]


def test_every_moves_names_a_metric_its_cells_report(real):
    e2e = {m["name"]: m for m in real.data["end_to_end"]}
    cells = set(real.cell_names())
    for m in real.data["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), m["name"]


def test_every_per_layer_metric_has_a_file_with_a_known_reader(real):
    for m in real.data["per_layer"]:
        path = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".json")
        with open(path) as f:
            spec = json.load(f)
        for key in ("unit", "layer", "moves", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(reducers.resolve(spec["reducer"]))


def test_every_cell_resolves_to_its_files_and_sizes(real):
    for name in real.cell_names():
        cell = real.cell(name)
        dims = manifest.model_dims(cell.config, cell.job, cell.chips)
        assert dims["head_dim"] == 128
        assert cell.job in ("train", "serve")
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_a_cell_whose_job_and_chips_have_no_reduced_entry_is_an_error(real):
    cell = real.cell("mistral7b-train-4k")
    with pytest.raises(manifest.ManifestError, match="reduced"):
        manifest.model_dims(cell.config, "serve", 1)
    assert manifest.model_dims(cell.config, "train", 1)["n_layers"] == 2
    if "train.4" in cell.config["reduced"]:
        assert manifest.model_dims(cell.config, "train", 4)["n_layers"] == 8


def test_unknown_cell_and_unknown_device_are_errors(real):
    with pytest.raises(manifest.ManifestError):
        real.cell("no-such-cell")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")
    v5e = peaks.peak("TPU v5 lite")
    assert (v5e.bf16_flops_per_s, v5e.hbm_bytes_per_s,
            v5e.hbm_bytes) == (197e12, 819e9, 16e9)


def test_check_catches_malformed_manifests(tmp_path):
    root = benchmark_tiny.make_root(tmp_path, cells=("tiny-train",))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        good = json.load(f)
    assert manifest.check(manifest.Manifest(root)) == []

    def faults(edit):
        data = json.loads(json.dumps(good))
        edit(data)
        with open(path, "w") as f:
            json.dump(data, f)
        return manifest.check(manifest.Manifest(root))

    assert faults(lambda d: d["end_to_end"][0].update(unit="tokens per s"))
    assert faults(lambda d: d["end_to_end"][0].update(bound=0.5))
    assert faults(lambda d: d["per_layer"][0].update(moves="nothing"))
    assert faults(lambda d: d["workloads"][-1].update(name="a b"))
    assert faults(lambda d: d["workloads"][-1].update(chips=2))
    assert faults(lambda d: d["configs"][0]["reduced"].append("hidden_size"))
    assert faults(lambda d: d.update(extra=1))


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    """What a later PR does: a fifth cell, a new traffic mix and a new
    per-layer metric with a reader of its own, and no file that was there
    is touched."""
    root = benchmark_tiny.make_root(tmp_path, cells=())
    before = {}
    for dirpath, _, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                before[path] = f.read()
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "traffic", "fifth-mix.json"), "w") as f:
        json.dump({**benchmark_tiny.TINY_TRAFFIC["tiny-batches"],
                   "name": "fifth-mix", "sequences_per_step": 4}, f)
    with open(os.path.join(base, "workloads", "fifth-cell.json"), "w") as f:
        cell = dict(benchmark_tiny.TINY_CELLS["tiny-train"],
                    name="fifth-cell")
        del cell["traffic"], cell["like"]
        json.dump(cell, f)
    with open(os.path.join(base, "layer_metrics", "steps.fifth.json"),
              "w") as f:
        json.dump({"name": "steps.fifth", "unit": "steps",
                   "reducer": "test_benchmark_manifest:count_steps"}, f)
    for path, content in before.items():
        if path.endswith("BENCHMARK.json"):
            continue
        with open(path, "rb") as f:
            assert f.read() == content, path
    data = json.loads(before[os.path.join(root, "BENCHMARK.json")])
    data["workloads"].append({"name": "fifth-cell", "config": "tiny",
                              "traffic": "fifth-mix", "chips": 1,
                              "why": "a later PR's cell"})
    for m in data["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("fifth-cell")
    data["per_layer"].append({
        "name": "steps.fifth", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "Train session",
        "moves": "train_tokens_per_s", "workloads": ["fifth-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    m = manifest.Manifest(root)
    assert manifest.check(m) == []
    cell = m.cell("fifth-cell")
    assert cell.traffic["sequences_per_step"] == 4
    ctx = reducers.Context(cell=cell, trace=None, counters={"steps": 7},
                           device_kind="cpu")
    assert reducers.evaluate(cell.per_layer, ctx) == {"steps.fifth": 7.0}


def count_steps(ctx, params):
    """The fifth cell's own reader."""
    return float(ctx.counters["steps"])
