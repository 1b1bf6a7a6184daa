"""The configuration of gated short convolutions, 64-wide grouped-query
attention and an expert layer without a shared expert, and its cell
(PR 41), on the CPU: `python -m pytest benchmark/tests -q`.  Nothing
here measures anything, and nothing here pins where the accepted entries
of BENCHMARK.json stand or how many there are."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [REPO, BENCH]

from harness import lookup  # noqa: E402

CELL = "lfm2_8b_a1b_s8192"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = {"short_conv_block_device_ms": "models",
               "short_conv_block_roofline_pct": "models",
               "head64_attention_device_ms": "kernels",
               "head64_attention_roofline_pct": "kernels",
               "ep4_moe_device_ms": "kernels",
               "head64_rotary_device_ms": "kernels",
               "lfm2_fwd_device_ms": "models",
               "lfm2_bwd_device_ms": "models",
               "lfm2_optimizer_device_ms": "optimizer",
               "lfm2_scope_unattributed_pct": "device",
               "lfm2_host_dispatch_ms": "one-program step, host side"}
# the accepted metrics whose readers the twins above import
TWINS = {"head64_attention_device_ms": "causal_attention_device_ms",
         "ep4_moe_device_ms": "moe_device_ms",
         "head64_rotary_device_ms": "rotary_device_ms",
         "lfm2_fwd_device_ms": "fwd_device_ms",
         "lfm2_bwd_device_ms": "bwd_device_ms",
         "lfm2_optimizer_device_ms": "optimizer_device_ms",
         "lfm2_scope_unattributed_pct": "scope_unattributed_pct",
         "lfm2_host_dispatch_ms": "host_dispatch_ms"}

_PERIOD = ["conv", "conv", "full_attention", "conv"]
# the `config` of the catalog's row for
# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": _PERIOD * 5 + ["conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
LAYERS_HELD = [0, *range(2, 14)]
HELD = {"num_hidden_layers": 13,
        "layer_types": [PUBLISHED["layer_types"][i] for i in LAYERS_HELD],
        "num_dense_layers": 1, "num_experts": 8, "vocab_size": 16384}

# by hand, from the widths above
D, HEADS, KV, HEAD, S, VOCAB = 2048, 32, 8, 64, 8192, 16384
CONV = D * 3 * D + D * D + 3 * D            # W_in, W_out, the taps
ATTENTION = 2 * D * D + 2 * D * KV * HEAD + 2 * HEAD    # q k v o, two gains
EXPERT = 3 * D * 1792
ROUTER = 32 * D
GAINS = 2 * D                               # a layer's two norms
CONVS, ATTENTIONS, SPARSE = 10, 3, 12
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
    # the floors: a whole period and four layers after the dense ones, 8
    # experts, an eighth of the vocabulary; attention to conv as
    # published, 1 : 3, in the layers after the dense one
    after = config["layer_types"][config["num_dense_layers"]:]
    assert len(after) >= 4 and after == ["full_attention", "conv", "conv",
                                         "conv"] * 3
    assert after.count("conv") == 3 * after.count("full_attention")
    assert PUBLISHED["layer_types"].count("conv") == 18
    assert config["layers_held"] == LAYERS_HELD
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert config["num_experts_published"] == 32
    for key in ("tied_head", "equations", "dense_width", "taps_layout",
                "router", "selection_bias", "init", "dtype", "optimizer",
                "data", "remat"):
        assert config["assumed"][key], key
    assert "expert-parallel 4" in config["deployment"]
    assert config["samples_unit"] == "sequences"
    assert cell.traffic["batch"] == 2 and cell.traffic["seq_len"] == S
    assert cell.traffic["resident"] is True
    assert cell.chips == 1 and cell.traffic_name == "s8192_lm_ep4_b2"
    entry, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "lfm2_8b_a1b", "s8192_lm_ep4_b2", 1)
    assert len(entry["why"]) <= 200


def test_parameters_held_are_the_issues_count_and_the_built_models(cell):
    assert CONV == 16_783_360 and ATTENTION == 10_485_888
    dense_layer = CONV + GAINS + 3 * D * 7168
    conv_sparse = CONV + GAINS + ROUTER + 8 * EXPERT
    attention_sparse = ATTENTION + GAINS + ROUTER + 8 * EXPERT
    assert dense_layer == 60_827_648
    assert conv_sparse == 104_933_376 and attention_sparse == 98_635_904
    held = (dense_layer + 9 * conv_sparse + 3 * attention_sparse
            + VOCAB * D + D)
    assert held == cell.config["parameters_held"] == 1_334_692_224
    # the issue's count for layers 0 and 2-9, which plan under 55%
    assert held - 3 * conv_sparse - attention_sparse == 921_256_192
    # the zoo's model at these sizes, shapes only (nothing is drawn)
    step = cell.model._step_block(cell.config)
    trained = {n: p for n, p in step.collect_params().items()
               if p.grad_req != "null"}
    assert sum(math.prod(p.shape) for p in trained.values()) == held
    # the head reads the embedding's array: one array, no head of its own
    assert sum(n.endswith("embed_weight") for n in trained) == 1
    assert not any(n.endswith("head_weight") for n in trained)
    assert not any("shared_" in n for n in trained)


def test_flops_and_bytes_are_counted_by_hand(cell):
    config, traffic = cell.config, cell.traffic
    core = HEADS * (HEAD + HEAD) * CAUSAL_PAIRS     # a layer, a sequence
    assert cell.model.attention_flops_per_sample(config, traffic) \
        == 6 * ATTENTIONS * core
    per_token = (
        CONVS * 4 * D * D
        + ATTENTIONS * (2 * D * D + 2 * D * KV * HEAD)
        + SPARSE * (ROUTER + 4 * 8 / 32 * EXPERT)
        + 3 * D * 7168 + D * VOCAB)
    want = 6 * (S * per_token + ATTENTIONS * core)
    got = cell.model.flops_per_sample(config, traffic)
    assert abs(got - want) / want < 1e-12, (got, want)
    assert 22.6e12 < got < 22.7e12, got             # 22.6 TFLOP a sequence
    macs = cell.model.forward_macs_per_token(config, S)
    assert round(sum(macs.values()) / 1e6, 1) == 460.1
    shares = {k: round(100 * v / sum(macs.values()), 1)
              for k, v in macs.items()}
    assert shares == {"conv_projections": 36.5, "attention_projections": 6.8,
                      "attention_cores": 10.9, "experts": 28.9, "dense": 9.6,
                      "head": 7.3}, shares
    # short_conv's floor: 11 passes over a stream and 3 over the taps, a
    # conv layer, in 2 bytes
    assert cell.model.short_conv_bytes_per_sample(config, traffic) \
        == CONVS * 2 * (11 * S * D + 3 * 3 * D)
    # 7.38 GB a step of two sequences: 9.0 ms at 819 GB/s
    byte_ms = 2 * cell.model.short_conv_bytes_per_sample(
        config, traffic) / 819e9 * 1e3
    assert round(byte_ms, 1) == 9.0
    # the conv operators' projections: W_in and W_out, forward + backward
    assert cell.model.short_conv_block_flops_per_sample(config, traffic) \
        == 6 * S * CONVS * (D * 3 * D + D * D)
    # 16.49 TFLOP a step: 83.7 ms at 197 TFLOP/s; the block's floor 92.7
    flop_ms = 2 * cell.model.short_conv_block_flops_per_sample(
        config, traffic) / 197e12 * 1e3
    assert round(flop_ms, 1) == 83.7 and round(flop_ms + byte_ms, 1) == 92.7


def test_rehearsal_keeps_every_kind_and_the_kernel_routes(cell):
    small = cell.config["rehearsal"]["model"]
    kinds = small["layer_types"]
    assert set(kinds) == {"conv", "full_attention"}
    assert len(kinds) == small["num_hidden_layers"] \
        > cell.config["num_dense_layers"]
    assert small["num_experts"] < small["num_experts_published"]
    assert cell.config["rehearsal"]["traffic"]["seq_len"] % 128 == 0
    # heads of 64 as published: `flash_causal` and the rotation kernel
    assert small["hidden_size"] // small["num_attention_heads"] == HEAD


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_new_readers_are_listed_for_this_cell_and_read_nothing_untraced(
        name):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "throughput"
    assert entry["source"] == "device_trace"
    assert entry["layer"] == NEW_METRICS[name]
    twin = next((m for m in MANIFEST["per_layer"]
                 if m["name"] == TWINS.get(name)), None)
    assert (entry["unit"], entry["better"]) == (
        (twin["unit"], twin["better"]) if twin
        else ("%", "higher") if name.endswith("_roofline_pct")
        else ("ms", "lower"))
    assert twin is None or entry["layer"] == twin["layer"]
    read = lookup.metric_reader("layer_metrics", name)
    assert read({"trace": None, "samples_per_step": 2, "chips": 1,
                 "peak": None}) is None


def test_the_block_reader_is_its_siblings_over_the_conv_scope():
    """One copy of the reader's code: `mtp_device_ms`'s, whose own
    pattern stays what it was."""
    block = lookup._module(BENCH, "layer_metrics",
                           "short_conv_block_device_ms.py")
    sibling = lookup._module(BENCH, "layer_metrics", "mtp_device_ms.py")
    assert block.read.__code__.co_filename == sibling.read.__code__.co_filename
    assert sibling._IN_BLOCK.search("jit(f)/jvp(m)/mtp/layer/dot")
    assert not sibling._IN_BLOCK.search("jit(f)/jvp(m)/conv/dot")
    pattern = block._reader._IN_BLOCK
    assert pattern.search("jit(f)/transpose(jvp(m))/layer2/conv/short_conv/mul")
    assert pattern.search("jit(f)/jvp(conv)/dot")
    assert not pattern.search("jit(f)/jvp(m)/layer2/short_conv/mul")
    assert not pattern.search("jit(f)/jvp(m)/causal_conv1d/mul")
    assert not pattern.search("jit(f)/jvp(m)/mtp/dot")


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
    assert set(facts["reference_rel_l2"]) == {"lm", "attention_operator"}
    routes, = [i["routes"] for i in info if "routes" in i]
    assert routes["attention"]["flash_causal"] >= 1
    assert routes["attention"]["reference"] == 0
    assert routes["attention"]["kernel_infer"] == 0
    assert routes["rotary"]["xla"] == 0 and routes["rotary"]["kernel"] >= 2
    assert routes["moe_experts"]["ragged_dot"] == 0
    moe, = [i["moe"] for i in info if "moe" in i]
    assert moe["dropped"] == 0 and set(moe["plan_chunks_a_layer"]) == {1}
