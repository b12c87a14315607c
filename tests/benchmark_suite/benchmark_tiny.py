"""A temporary copy of the benchmark's data at toy sizes, for the CPU tests.

The copy has the repository's ``BENCHMARK.json`` and data files untouched,
plus a tiny configuration, tiny traffic mixes and a tiny cell for each job,
added the way a later PR adds a cell: new files and new entries, no edit to
a file that is there.
"""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny", "source": "tests only",
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
    "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
    "reduced": {"train.1": {"num_hidden_layers": 2, "why": "tests"},
                "train.4": {"num_hidden_layers": 3, "why": "tests"},
                "serve.1": {"why": "nothing cut"}},
}
TINY_TRAFFIC = {
    "tiny-batches": {"name": "tiny-batches", "kind": "token_batches",
                     "seq_len": 32, "sequences_per_step": 2,
                     "dataset_rows": 8},
    "tiny-batches-x4": {"name": "tiny-batches-x4", "kind": "token_batches",
                        "seq_len": 32, "sequences_per_step": 4,
                        "dataset_rows": 8},
    "tiny-open": {"name": "tiny-open", "kind": "requests", "loop": "open",
                  "pattern_seed": 1,
                  "rate_rps": 20.0, "preroll_s": 0.5, "timeout_s": 20.0,
                  "prompt_len": {"dist": "lognormal", "median": 12,
                                 "sigma": 0.6, "min": 2, "max": 32}},
    "tiny-closed": {"name": "tiny-closed", "kind": "requests",
                    "loop": "closed", "pattern_seed": 1, "clients": 4,
                    "n_lengths": 64,
                    "preroll_s": 0.5, "timeout_s": 20.0,
                    "prompt_len": {"dist": "lognormal", "median": 12,
                                   "sigma": 0.6, "min": 2, "max": 32}},
}
_DEPLOYMENT = {"max_batch_size": 4, "pad_batch_to": [2, 4],
               "batch_wait_timeout_s": 0.01, "length_buckets": [16, 32],
               "route": "/score"}
_MODEL = {"dtype": "float32", "use_flash": False}
TINY_CELLS = {
    "tiny-train": {"name": "tiny-train", "job": "train", "chips": 1,
                   "model": {**_MODEL, "remat": True},
                   "reference": {"sequences": 2, "seq_len": 16},
                   "traffic": "tiny-batches", "like": "mistral7b-train-4k"},
    "tiny-train-fsdp4": {"name": "tiny-train-fsdp4", "job": "train",
                         "chips": 4, "mesh": {"data": 1, "fsdp": 4},
                         "model": {**_MODEL, "remat": True},
                         "reference": {"sequences": 4, "seq_len": 16},
                         "traffic": "tiny-batches-x4",
                         "like": "mistral7b-train-4k-fsdp4"},
    "tiny-serve-open": {"name": "tiny-serve-open", "job": "serve",
                        "chips": 1, "deployment": _DEPLOYMENT,
                        "model": _MODEL,
                        "reference": {"prompt_lengths": [3, 16, 20, 32],
                                      "prompts_per_length": 1},
                        "traffic": "tiny-open",
                        "like": "internlm2-serve-steady"},
    "tiny-serve-closed": {"name": "tiny-serve-closed", "job": "serve",
                          "chips": 1, "deployment": _DEPLOYMENT,
                          "model": _MODEL,
                          "reference": {"prompt_lengths": [3, 32],
                                        "prompts_per_length": 2},
                          "traffic": "tiny-closed",
                          "like": "internlm2-serve-offline"},
}


def _dump(path, data):
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make_root(tmp_path, cells=tuple(TINY_CELLS)) -> str:
    """Copy the benchmark's data under ``tmp_path`` and add the tiny cells
    ``cells`` beside the real ones. Returns the copy's root."""
    root = str(tmp_path)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for kind in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", kind),
                        os.path.join(root, "benchmark", kind))
    _dump(os.path.join(root, "benchmark", "configs", "tiny.json"),
          TINY_CONFIG)
    manifest["configs"].append({
        "name": "tiny", "source": "tests only",
        "file": "benchmark/configs/tiny.json",
        "reduced": ["num_hidden_layers"], "why": "a toy for the CPU tests"})
    for name in cells:
        cell = dict(TINY_CELLS[name])
        traffic, like = cell.pop("traffic"), cell.pop("like")
        _dump(os.path.join(root, "benchmark", "traffic", traffic + ".json"),
              TINY_TRAFFIC[traffic])
        _dump(os.path.join(root, "benchmark", "workloads", name + ".json"),
              cell)
        manifest["workloads"].append({
            "name": name, "config": "tiny", "traffic": traffic,
            "chips": cell["chips"], "why": "a toy for the CPU tests"})
        # the tiny cell reports what the real cell it is like reports
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            if like in metric.get("workloads", ()):
                metric["workloads"].append(name)
    _dump(os.path.join(root, "BENCHMARK.json"), manifest)
    return root
