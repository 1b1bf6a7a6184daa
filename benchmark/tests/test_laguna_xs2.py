"""The window / full attention configuration and its cell (PR 31), on the
CPU: `python -m pytest benchmark/tests -q`.  Nothing here measures
anything."""
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

CELL = "laguna_xs2_s8192"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = ("window_attention_device_ms", "window_attention_roofline_pct",
               "full_attention_device_ms", "gated_moe_device_ms",
               "rotary_device_ms")
# what the manifest listed for single cells before this PR
EARLIER = ("nemotron3_super_s8192",)

_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# the `config` of the catalog's row for
# https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json
PUBLISHED = {
    "model_type": "laguna",
    "vocab_size": 100352,
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 40,
    "num_attention_heads": 48,
    "num_key_value_heads": 8,
    "head_dim": 128,
    "max_position_embeddings": 262144,
    "attention_bias": False,
    "rms_norm_eps": 1e-06,
    "num_experts": 256,
    "num_experts_per_tok": 8,
    "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False,
    "gating": True,
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": _PERIOD * 10,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
CUTS = {"num_hidden_layers": (40, 9), "num_experts": (256, 32),
        "vocab_size": (100352, 12544)}


@pytest.fixture(scope="module")
def cell():
    return lookup.cell(CELL)


def test_every_published_key_is_there_and_only_the_three_cuts_differ(cell):
    config = cell.config
    assert sorted(config["reduced"]) == sorted(CUTS)
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == cell.config_name]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in CUTS:
            published, held = CUTS[key]
            assert value == published and config[key] == held, key
            assert config["published"][key] == published, key
        else:
            assert config[key] == value, key
    assert config["num_experts_published"] == 256
    # the floors: a whole period and >= 4 layers after the leading dense
    # layer, >= 8 experts, >= 1/8 of the vocabulary
    n = config["num_hidden_layers"]
    kinds = config["layer_types"][:n]
    assert kinds[1:] == (_PERIOD[1:] + _PERIOD[:1]) * 2
    assert 10 * kinds.count("sliding_attention") == 30 * (
        kinds.count("full_attention") - 1)          # layer 0 counted once
    assert config["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 8
    assert config["num_attention_heads_per_layer"][:n] == \
        [48, 64, 64, 64, 48, 64, 64, 64, 48]
    assert config["num_experts"] >= 8
    assert 8 * config["vocab_size"] >= PUBLISHED["vocab_size"]
    for key in ("expert_form", "head_gate", "router", "selection_bias",
                "qk_norm", "rotary", "init", "dtype", "data", "remat"):
        assert config["assumed"][key], key
    assert "8 chips share each layer" in config["deployment"]
    assert "expert-parallel 8" in config["deployment"]
    assert cell.traffic["batch"] == 2 and cell.traffic["seq_len"] == 8192
    assert cell.chips == 1


def _parameters(config, layers, experts, vocab):
    """(all parameters of `layers` layers holding `experts` experts a
    sparse layer, those a token's forward uses at top-k, the embedding
    among them as the published count has it); norms counted, the
    selection biases not."""
    d, hd, kv = config["hidden_size"], config["head_dim"], \
        config["num_key_value_heads"]
    expert = 3 * d * config["moe_intermediate_size"]
    shared = 3 * d * config["shared_expert_intermediate_size"]
    router = d * PUBLISHED["num_experts"]
    total = active = 2 * vocab * d + d
    for heads, mlp in zip(config["num_attention_heads_per_layer"][:layers],
                          config["mlp_layer_types"][:layers]):
        attention = d * (2 * hd * (heads + kv) + heads) + 2 * d
        total += attention
        active += attention
        if mlp == "dense":
            total += 3 * d * config["intermediate_size"]
            active += 3 * d * config["intermediate_size"]
        else:
            total += experts * expert + shared + router
            active += (config["num_experts_per_tok"] * expert * experts
                       / PUBLISHED["num_experts"] + shared + router)
    return total, active


def test_parameters_held_and_published_are_the_issues_counts(cell):
    config = cell.config
    held, _ = _parameters(config, 9, 32, 12544)
    assert held == config["parameters_held"] == 1_252_071_424
    whole, active = _parameters(config, 40, 256, 100352)
    assert abs(whole - 33.44e9) < 0.005e9, whole
    assert abs(active - 3.02e9) < 0.005e9, active
    # two-matrix experts or an elementwise gate would not give 33.44 B
    assert abs(whole - 39 * 256 * 2048 * 512 - 22.9e9) < 0.1e9
    assert abs(whole + 127 * 2048 * (10 * 48 + 30 * 64) - 34.07e9) < 0.01e9


def test_flops_per_sample_against_six_times_active_parameters(cell):
    """6 x (the parameters a token's forward multiplies by, at this
    chip's share of the experts) x S, plus the cores, which no parameter
    carries: the causal pairs counted exactly."""
    config, traffic = cell.config, cell.traffic
    s = traffic["seq_len"]
    _, active = _parameters(config, 9, 32, 12544)
    # the embedding's rows and the norms multiply nothing
    norms = (19 + 12544) * config["hidden_size"]
    pairs_full = s * (s + 1) // 2
    pairs_window = 512 * 513 // 2 + (s - 512) * 512
    cores = 2 * 128 * (3 * 48 * pairs_full + 6 * 64 * pairs_window)
    want = 6 * (s * (active - norms) + cores)
    got = cell.model.flops_per_sample(config, traffic)
    assert abs(got - want) / want < 1e-9, (got, want)
    assert 31.74e12 < got < 31.76e12, got           # 31.75 TFLOP a sequence
    macs = cell.model.forward_macs_per_token(config, s)
    total = sum(macs.values())
    assert round(total / 1e6, 1) == 646.0
    shares = {k: round(100 * v / total, 1) for k, v in macs.items()}
    assert shares == {"projections": 48.9, "full_cores": 23.4,
                      "window_cores": 7.5, "experts": 8.4, "dense": 7.8,
                      "head": 4.0}
    assert cell.model.window_attention_flops_per_sample(config, traffic) \
        == 6 * 2 * 128 * 6 * 64 * pairs_window


def test_rehearsal_keeps_every_layer_kind_both_rotary_kinds_and_a_share(
        cell):
    small = cell.config["rehearsal"]["model"]
    n = small["num_hidden_layers"]
    assert set(cell.config["layer_types"][:n]) == {"full_attention",
                                                   "sliding_attention"}
    assert set(cell.config["mlp_layer_types"][:n]) == {"dense", "sparse"}
    assert len(set(small["num_attention_heads_per_layer"])) == 2
    assert small["num_experts_per_tok"] > 1
    assert small["num_experts"] < small["num_experts_published"]
    assert small["rope_parameters"]["full_attention"]["rope_type"] == "yarn"
    assert small["sliding_window"] < \
        cell.config["rehearsal"]["traffic"]["seq_len"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_are_listed_for_this_cell_and_read_nothing_untraced(
        name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "throughput"
    assert entry["source"] == "device_trace" and entry["layer"] == "kernels"
    read = lookup.metric_reader("layer_metrics", name)
    assert read({"trace": None, "samples_per_step": 2, "chips": 1,
                 "peak": None}) is None


def test_the_accepted_lists_are_as_they_were():
    """Every list accepted before this PR stays on its own cells."""
    for m in MANIFEST["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert tuple(names[-len(NEW_METRICS):]) == NEW_METRICS
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["workloads"][-2]["name"] in EARLIER
    assert MANIFEST["configs"][-1]["name"] == "laguna_xs2"


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
    moe, = [i["moe"] for i in infos if "moe" in i]
    assert moe["dropped"] == 0 and moe["assignments_on_held_experts"] > 0
    assert len(moe["plan_chunks_a_layer"]) == 4
    routes, = [i["routes"] for i in infos if "routes" in i]
    assert routes["attention"]["splash_window"] == 3
    assert routes["attention"]["flash_causal"] == 2
    assert routes["moe_experts"]["grouped_kernel"] == 8
