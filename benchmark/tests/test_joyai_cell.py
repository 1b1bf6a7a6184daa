"""The latent-attention configuration with a multi-token-prediction
module and its cell (PR 39), on the CPU: `python -m pytest
benchmark/tests -q`.  Nothing here measures anything, and nothing here
pins where the accepted entries of BENCHMARK.json stand or how many
there are."""
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

CELL = "joyai_llm_flash_s8192"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = {"mla_attention_device_ms": "kernels",
               "mla_attention_roofline_pct": "kernels",
               "mla_projection_device_ms": "kernels",
               "mtp_device_ms": "models"}

# the `config` of the catalog's row for
# https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}
HELD = {"num_hidden_layers": 5, "n_routed_experts": 32, "vocab_size": 16160}
SPARSE = 4                  # sparse layers of the main stack held
LATENT = 1 + SPARSE + 1     # latent layers: dense, sparse, the module's

# by hand, from the widths above
D, HEADS, S, VOCAB = 2048, 32, 8192, 16160
MLA = (D * 1536 + 1536 * HEADS * 192 + D * 576 + 512 * HEADS * 256
       + HEADS * 128 * D)
NORMS = 1536 + 512 + 2 * D              # the inner two, the layer's two
EXPERT = 3 * D * 768
DENSE_LAYER = MLA + NORMS + 3 * D * 7168
SPARSE_LAYER = MLA + NORMS + 256 * D + EXPERT + 32 * EXPERT
MODULE = 2 * D * D + SPARSE_LAYER + 3 * D
CAUSAL_PAIRS = S * (S + 1) // 2


@pytest.fixture(scope="module")
def cell():
    return lookup.cell(CELL)


def test_every_published_key_is_there_and_only_the_cut_differs(cell):
    config = cell.config
    assert sorted(config["reduced"]) == sorted(HELD)
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == cell.config_name]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key, value in PUBLISHED.items():
        if key in HELD:
            assert config["published"][key] == value, key
            assert config[key] == HELD[key], key
        else:
            assert config[key] == value, key
    # the floors: four sparse layers after the dense one, 8 experts, an
    # eighth of the vocabulary, and the module stays
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["n_routed_experts_published"] == 256
    for key in ("equations", "latent_layout", "mtp_join", "mtp_loss_weight",
                "router", "selection_bias", "init", "dtype", "data", "remat"):
        assert config["assumed"][key], key
    assert config["mtp_loss_weight"] == 0.3
    assert "expert-parallel 8" in config["deployment"]
    assert config["samples_unit"] == "sequences"
    assert cell.traffic["batch"] == 2 and cell.traffic["seq_len"] == S
    assert cell.chips == 1 and cell.traffic_name == "s8192_lm_mtp_b2"
    entry, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "joyai_llm_flash", "s8192_lm_mtp_b2", 1)


def test_parameters_held_are_the_issues_count_and_the_built_models(cell):
    assert MLA == 26_345_472
    assert DENSE_LAYER == 70_391_808 and SPARSE_LAYER == 182_589_440
    assert MODULE == 190_984_192
    held = DENSE_LAYER + SPARSE * SPARSE_LAYER + MODULE + 2 * VOCAB * D + D
    assert held == cell.config["parameters_held"] == 1_057_927_168
    # the issue's count for five sparse layers, which the 80% rule refused
    assert held + SPARSE_LAYER == 1_240_516_608
    # the zoo's model at these sizes, shapes only (nothing is drawn)
    step = cell.model._step_block(cell.config)
    trained = {n: p for n, p in step.collect_params().items()
               if p.grad_req != "null"}
    assert sum(int(__import__("math").prod(p.shape))
               for p in trained.values()) == held
    shared = [n for n in trained
              if n.endswith(("embed_weight", "head_weight"))]
    assert len(shared) == 2, shared         # one array each, no copy


def test_flops_are_counted_by_hand(cell):
    config, traffic = cell.config, cell.traffic
    core = HEADS * (192 + 128) * CAUSAL_PAIRS       # a layer, a sequence
    assert cell.model.latent_attention_flops_per_sample(config, traffic) \
        == 6 * LATENT * core
    per_token = (
        LATENT * MLA                                 # the projections
        + (SPARSE + 1) * (256 * D + EXPERT + 8 * 32 / 256 * EXPERT)
        + 3 * D * 7168 + 2 * D * D + 2 * D * VOCAB)
    want = 6 * (S * per_token + LATENT * core)
    got = cell.model.flops_per_sample(config, traffic)
    assert abs(got - want) / want < 1e-12, (got, want)
    assert 28.3e12 < got < 28.5e12, got             # 28.4 TFLOP a sequence
    macs = cell.model.forward_macs_per_token(config, S)
    assert round(sum(macs.values()) / 1e6, 1) == 578.2
    shares = {k: round(100 * v / sum(macs.values()), 1)
              for k, v in macs.items()}
    assert shares == {"latent_projections": 27.3, "latent_cores": 43.5,
                      "experts": 8.6, "dense": 7.6, "join": 1.5,
                      "heads": 11.4}, shares
    # the module: its join, its layer, its pass through the head
    module = (2 * D * D + MLA + core / S
              + 256 * D + 2 * EXPERT + D * VOCAB)
    assert round(100 * module / sum(macs.values())) == 21


def test_rehearsal_keeps_every_kind_and_the_kernel_route(cell):
    small = cell.config["rehearsal"]["model"]
    assert small["num_hidden_layers"] > cell.config["first_k_dense_replace"]
    assert small["n_routed_experts"] < small["n_routed_experts_published"]
    assert cell.config["rehearsal"]["traffic"]["seq_len"] % 128 == 0
    # heads of 192 and 128 as published: `latent_splash` is chosen
    assert "qk_nope_head_dim" not in small and "v_head_dim" not in small


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_new_readers_are_listed_for_this_cell_and_read_nothing_untraced(
        name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "throughput"
    assert entry["source"] == "device_trace"
    assert entry["layer"] == NEW_METRICS[name]
    read = lookup.metric_reader("layer_metrics", name)
    assert read({"trace": None, "samples_per_step": 2, "chips": 1,
                 "peak": None}) is None


def test_the_accepted_lists_do_not_name_this_cell():
    for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]


def test_rehearsal_ends_with_a_well_formed_line():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, cwd=REPO,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"throughput", "mfu_pct", "setup_s"}
    assert all(m["value"] is None for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    info = [json.loads(ln[len("[info] "):])
            for ln in p.stdout.splitlines() if ln.startswith("[info] ")]
    facts, = [i for i in info if "reference_rel_l2" in i]
    assert set(facts["reference_rel_l2"]) == {"lm", "mtp"}
    routes, = [i["routes"] for i in info if "routes" in i]
    assert routes["attention"]["latent_splash"] >= 4
    assert routes["attention"]["latent_xla"] == 0
    moe, = [i["moe"] for i in info if "moe" in i]
    assert moe["dropped"] == 0 and set(moe["plan_chunks_a_layer"]) == {1}
