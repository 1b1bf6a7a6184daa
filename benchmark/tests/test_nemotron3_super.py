"""The hybrid-decoder configuration and its cell (PR 27), on the CPU:
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

CELL = "nemotron3_super_s8192"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = ("ssd_device_ms", "moe_device_ms",
               "causal_attention_device_ms", "ssd_roofline_pct")

# the `config` of the catalog's row for
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json
PUBLISHED = {
    "attention_bias": False,
    "chunk_size": 128,
    "conv_kernel": 4,
    "expand": 2,
    "head_dim": 128,
    "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64,
    "mamba_hidden_act": "silu",
    "mamba_num_heads": 128,
    "mamba_proj_bias": False,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h",
    "moe_intermediate_size": 2688,
    "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E",
    "n_group": 1,
    "n_groups": 8,
    "n_routed_experts": 512,
    "n_shared_experts": 1,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts_per_tok": 22,
    "num_hidden_layers": 88,
    "num_key_value_heads": 2,
    "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True,
    "residual_in_fp32": False,
    "rope_theta": 10000,
    "routed_scaling_factor": 5,
    "sliding_window": None,
    "ssm_state_size": 128,
    "tie_word_embeddings": False,
    "time_step_floor": 0.0001,
    "time_step_max": 0.1,
    "time_step_min": 0.001,
    "topk_group": 1,
    "use_bias": False,
    "use_conv_bias": True,
    "use_mamba_kernels": True,
    "vocab_size": 131072
}
CUTS = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 8),
        "vocab_size": (131072, 16384), "num_nextn_predict_layers": (1, 0)}


@pytest.fixture(scope="module")
def cell():
    return lookup.cell(CELL)


def test_every_published_key_is_there_and_only_the_four_cuts_differ(cell):
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
    # one whole period of the published pattern, in its published ratio
    first, last = config["layers_held"]
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert config["pattern_held"] == pattern[first:last + 1]
    assert len(config["pattern_held"]) == config["num_hidden_layers"]
    assert [config["pattern_held"].count(k) * 8 for k in "ME*"] == \
        [pattern.count(k) for k in "ME*"]
    assert config["n_routed_experts_published"] == 512
    for key in ("rotary", "latent_placement", "selection_bias",
                "multi_token_prediction", "init", "dtype", "data", "remat"):
        assert config["assumed"][key], key
    assert "512 v5e chips" in config["deployment"]
    assert "expert-parallel 64" in config["deployment"]


def test_rehearsal_keeps_every_layer_kind_top_k_and_a_share(cell):
    small = cell.config["rehearsal"]["model"]
    assert set(small["pattern_held"]) == set("ME*")
    assert small["num_experts_per_tok"] > 1
    assert small["n_routed_experts"] < small["n_routed_experts_published"]


def _parameters_held(config):
    """(all parameters held, those a token's forward multiplies by)."""
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    mamba = (d * (inner + conv + config["mamba_num_heads"]) + inner * d
             + conv * (config["conv_kernel"] + 1)
             + 3 * config["mamba_num_heads"] + inner + d)
    hd = config["head_dim"]
    attention = (2 * d * hd * (config["num_attention_heads"]
                               + config["num_key_value_heads"]) + d)
    expert = 2 * config["moe_latent_size"] * config["moe_intermediate_size"]
    routed = config["n_routed_experts_published"]
    outside = (d * routed + routed + 2 * d * config["moe_latent_size"]
               + 2 * d * config["moe_shared_expert_intermediate_size"] + d)
    counts = {k: config["pattern_held"].count(k) for k in "M*E"}
    ends = 2 * config["vocab_size"] * d + d
    held = (counts["M"] * mamba + counts["*"] * attention
            + counts["E"] * (outside + config["n_routed_experts"] * expert)
            + ends)
    per_token = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / routed
    active = (counts["M"] * mamba + counts["*"] * attention
              + counts["E"] * (outside + per_token * expert)
              + config["vocab_size"] * d)       # the head; no embedding
    return held, active


def test_parameters_held_are_the_issues_count(cell):
    held, _ = _parameters_held(cell.config)
    assert abs(held - 1.211e9) < 0.001e9, held


def test_flops_per_sample_against_six_times_active_parameters(cell):
    """6 x (the parameters a token's forward multiplies by) x S, plus
    the two terms no parameter carries: causal attention's S^2 at S / 2
    keys a query, and the scan's four products."""
    config, traffic = cell.config, cell.traffic
    s = traffic["seq_len"]
    _, active = _parameters_held(config)
    attention = (2 * (s // 2) * config["num_attention_heads"]
                 * config["head_dim"])
    scan = cell.model.ssd_macs_per_token(config)
    counts = {k: config["pattern_held"].count(k) for k in "M*E"}
    want = 6 * s * (active + counts["*"] * attention + counts["M"] * scan)
    got = cell.model.flops_per_sample(config, traffic)
    assert abs(got - want) / want < 0.02, (got, want)
    assert 48.0e12 < got < 48.6e12, got             # 48.3 TFLOP a sequence
    # the shares the cell's `why` states
    macs = cell.model.forward_macs_per_token(config, s)
    total = sum(macs[k] * counts[k] for k in "M*E") + macs["head"]
    assert round(100 * counts["M"] * macs["M"] / total) == 57
    assert round(100 * counts["E"] * macs["E"] / total) == 29
    assert round(100 * macs["*"] / total) == 7
    assert cell.model.ssd_flops_per_sample(config, traffic) == \
        6 * scan * counts["M"] * s


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_are_listed_for_this_cell_and_read_nothing_untraced(
        name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "throughput"
    read = lookup.metric_reader("layer_metrics", name)
    assert read({"trace": None, "samples_per_step": 1, "chips": 1,
                 "peak": None}) is None


def test_the_accepted_lists_are_as_they_were():
    """PR 24's listed metrics stay on their four cells."""
    for m in MANIFEST["per_layer"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]


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
    routes, = [i["routes"] for i in infos if "routes" in i]
    assert routes["attention"]["flash_causal"] == 1
    assert routes["ssd_scan"]["chunked_xla"] == 2
    assert routes["moe_experts"]["grouped_kernel"] == 4
