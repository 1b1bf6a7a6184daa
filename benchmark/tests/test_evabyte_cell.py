"""The byte-level EVA configuration and its cell (PR 34), on the CPU:
`python -m pytest benchmark/tests -q`.  Nothing here measures anything."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [REPO, BENCH]

from harness import lookup  # noqa: E402

CELL = "evabyte_s32768"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = ("eva_attention_device_ms", "eva_attention_roofline_pct",
               "eva_summary_device_ms")

# the `config` of the catalog's row for
# https://huggingface.co/EvaByte/EvaByte/blob/main/config.json
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048,
}
DEPTH = 4
LAYER = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128 + 2 * 4096
OUTSIDE = 320 * 4096 + 8 * 320 * 4096 + 4096    # embedding, head, norm
# a head's visible (query, key) pairs at S 32768, W 2048, C 16
LOCAL, REMOTE = 33_570_816, 31_457_280


@pytest.fixture(scope="module")
def cell():
    return lookup.cell(CELL)


def test_every_published_key_is_there_and_only_the_depth_differs(cell):
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers"]
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == cell.config_name]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert value == config["published"][key] == 32
            assert config[key] == DEPTH >= 4        # the floor
        else:
            assert config[key] == value, key
    for key in ("equations", "rotary_before_pooling", "pooling_scale",
                "mu_on_the_key", "remote_set", "multibyte_head",
                "fp32_logits", "fp32_skip_add", "init", "dtype", "data",
                "remat"):
        assert config["assumed"][key], key
    assert "no layer divided" in config["deployment"]
    assert config["samples_unit"] == "sequences"
    assert cell.traffic["batch"] == 1 and cell.traffic["seq_len"] == 32768
    assert cell.traffic["seq_len"] == config["max_seq_length"]
    assert cell.chips == 1


def test_parameters_held_and_published_are_the_issues_counts(cell):
    assert LAYER == 202_391_552
    assert DEPTH * LAYER + OUTSIDE == cell.config["parameters_held"] \
        == 821_366_784
    assert 5 * LAYER + OUTSIDE == 1_023_758_336     # the issue's depth
    whole = 32 * LAYER + OUTSIDE
    assert abs(whole - 6.488e9) < 0.0005e9, whole   # the published 6.5 B


def test_visible_pairs_and_flops_are_counted_exactly(cell):
    config, traffic = cell.config, cell.traffic
    s = traffic["seq_len"]
    assert cell.model.visible_pairs(config, s) == (16, LOCAL, REMOTE)
    assert LOCAL + REMOTE == 65_028_096
    # by the definition, a query at a time
    window, chunk = config["window_size"], config["chunk_size"]
    assert LOCAL == sum(i % window + 1 for i in range(s))
    assert REMOTE == sum((i // window) * (window // chunk)
                         for i in range(s))
    # 6 x (the parameters a token's forward multiplies by) x S, plus the
    # cores, which no parameter carries
    matrices = DEPTH * (LAYER - 2 * 4096 - 2 * 32 * 128) + 8 * 320 * 4096
    cores = DEPTH * 2 * 32 * 128 * (LOCAL + REMOTE)
    want = 6 * (s * matrices + cores)
    got = cell.model.flops_per_sample(config, traffic)
    assert abs(got - want) / want < 1e-12, (got, want)
    assert 173.9e12 < got < 174.1e12, got           # 174.0 TFLOP a sequence
    assert cell.model.eva_attention_flops_per_sample(config, traffic) \
        == 6 * cores
    macs = cell.model.forward_macs_per_token(config, s)
    shares = {k: round(100 * v / sum(macs.values()), 1)
              for k, v in macs.items()}
    assert shares == {"projections": 30.3, "mlp": 61.1, "eva_cores": 7.3,
                      "head": 1.2}, shares


def test_rehearsal_keeps_several_windows_and_the_kernel_route(cell):
    small = cell.config["rehearsal"]
    model, s = small["model"], small["traffic"]["seq_len"]
    assert s // model["window_size"] >= 3
    assert model["window_size"] % model["chunk_size"] == 0
    assert model["hidden_size"] // model["num_attention_heads"] == 128
    assert (s // model["chunk_size"]) % 128 == 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_are_listed_for_this_cell_and_read_nothing_untraced(
        name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "throughput"
    assert entry["source"] == "device_trace" and entry["layer"] == "kernels"
    read = lookup.metric_reader("layer_metrics", name)
    assert read({"trace": None, "samples_per_step": 1, "chips": 1,
                 "peak": None}) is None


def test_the_accepted_lists_are_as_they_were():
    """Every list accepted before this PR stays on its own cells; the
    new entries are the last of their lists."""
    for m in MANIFEST["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert tuple(names[-len(NEW_METRICS):]) == NEW_METRICS
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["workloads"][-2]["name"] == "laguna_xs2_s8192"
    assert MANIFEST["configs"][-1]["name"] == "evabyte"
    assert len(MANIFEST["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def _run(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *argv],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_the_contracted_line_for_the_new_cell(trace):
    r = _run("--workload", CELL, "--seed", "2147483999", "--seconds", "1",
             "--trace", trace, "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert last["correct"] is True and last["failed"] == 0
    kind = "per_layer" if trace == "1" else "end_to_end"
    want = [m["name"] for m in MANIFEST[kind]
            if CELL in m.get("workloads", [CELL])]
    assert list(last["metrics"]) == want
    if trace == "1":
        assert set(NEW_METRICS) <= set(want)
    assert all(m["value"] is None for m in last["metrics"].values())
    infos = [json.loads(ln[len("[info] "):]) for ln in lines[:-1]]
    assert all(infos[-1]["checks"].values()), infos[-1]["checks"]
    eva, = [i for i in infos if "eva" in i]
    # forward for the sample; the step program is traced after this line
    assert eva["routes"]["attention"]["eva_splash"] == 2
    assert eva["routes"]["attention"]["eva_xla"] == 0
    assert eva["routes"]["attention"]["reference"] == 0
    assert eva["eva"] == {"windows": 4, "chunks": 128,
                          "visible_pairs_a_head": {"local": 33024,
                                                   "remote": 24576}}
