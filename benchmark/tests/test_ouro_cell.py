"""The configuration whose layers run four times and its cell (PR 46), on
the CPU: `python -m pytest benchmark/tests -q`.  Nothing here measures
anything, and nothing here pins where the accepted entries of
BENCHMARK.json stand or how many there are: the cell and its metrics are
found by name."""
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

CELL = "ouro_2_6b_s8192"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = {"loop_pass_device_ms": "models",
               "exit_device_ms": "models",
               "exit_roofline_pct": "models",
               "mha128_attention_device_ms": "kernels",
               "mha128_attention_roofline_pct": "kernels",
               "ouro_rotary_device_ms": "kernels",
               "ouro_fwd_device_ms": "models",
               "ouro_bwd_device_ms": "models",
               "ouro_optimizer_device_ms": "optimizer",
               "ouro_scope_unattributed_pct": "device",
               "ouro_host_dispatch_ms": "one-program step, host side"}
# the accepted metrics whose readers the twins above import
TWINS = {"mha128_attention_device_ms": "causal_attention_device_ms",
         "ouro_rotary_device_ms": "rotary_device_ms",
         "ouro_fwd_device_ms": "fwd_device_ms",
         "ouro_bwd_device_ms": "bwd_device_ms",
         "ouro_optimizer_device_ms": "optimizer_device_ms",
         "ouro_scope_unattributed_pct": "scope_unattributed_pct",
         "ouro_host_dispatch_ms": "host_dispatch_ms"}

# the `config` of the catalog's row for
# https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}

# by hand, from the widths above
D, HEADS, HEAD, MLP, S, VOCAB, STEPS = 2048, 16, 128, 5632, 8192, 49152, 4
LAYER = 4 * D * D + 3 * D * MLP + 4 * D     # q k v o, the MLP, four gains
OUTSIDE = 2 * VOCAB * D + D + (D + 1)       # embedding, head, norm, gate
CAUSAL_PAIRS = S * (S + 1) // 2


@pytest.fixture(scope="module")
def cell():
    return lookup.cell(CELL)


def test_every_published_key_is_there_and_only_the_depth_differs(cell):
    config = cell.config
    depth = config["num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == cell.config_name]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    # whole stages of a ring: the depth divides 48; the floor of five
    assert depth in (12, 8, 6) and 48 % depth == 0 and depth >= 5
    assert config["layer_types"] == ["full_attention"] * depth
    assert config["layers_held"] == [0, depth - 1]
    for key in ("sandwich_norms", "norm_between_passes", "no_bias_no_qk_norm",
                "gate", "exit_entropy_beta", "objective", "decoding",
                "init", "dtype", "optimizer", "data", "remat"):
        assert config["assumed"][key], key
    assert config["exit_entropy_beta"] > 0 and config["init_std"] == 0.02
    assert "pipeline" in config["deployment"]
    assert f"{48 // depth} stages" in config["deployment"]
    for reading in ("12 layers", "8 layers", "6 layers"):
        assert reading in config["deployment"], reading
    assert config["samples_unit"] == "sequences"
    assert cell.traffic["batch"] == 1 and cell.traffic["seq_len"] == S
    assert cell.traffic["resident"] is True
    assert cell.chips == 1 and cell.traffic_name == "s8192_lm_loop4_b1"
    entry, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ouro_2_6b", "s8192_lm_loop4_b1", 1)
    assert len(entry["why"]) <= 200


def test_parameters_published_and_held_are_the_built_models(cell):
    assert LAYER == 51_388_416 and OUTSIDE == 201_330_689
    assert 48 * LAYER + OUTSIDE == 2_667_974_657
    assert "2,667,974,657" in cell.config["published"]["parameters"]
    assert {12: 817_991_681, 8: 612_438_017, 6: 509_661_185} == {
        n: n * LAYER + OUTSIDE for n in (12, 8, 6)}
    depth = cell.config["num_hidden_layers"]
    held = depth * LAYER + OUTSIDE
    assert held == cell.config["parameters_held"]
    # the zoo's model at these sizes, shapes only (nothing is drawn)
    params = cell.model._step_block(cell.config).collect_params()
    assert all(p.grad_req != "null" for p in params.values())
    assert sum(math.prod(p.shape) for p in params.values()) == held
    assert len(params) == 11 * depth + 5
    # one untied head, one gate, one final norm: read at all four exits
    for tail in ("embed_weight", "exit_head_weight", "exit_gate_weight",
                 "exit_gate_bias", "final_norm_weight"):
        assert sum(n.endswith(tail) for n in params) == 1, tail


def test_flops_and_the_two_floors_are_counted_by_hand(cell):
    config, traffic = cell.config, cell.traffic
    depth = config["num_hidden_layers"]
    application = 4 * D * D + 3 * D * MLP       # a token, multiply-adds
    assert application == 51_380_224
    core = HEADS * (HEAD + HEAD) * CAUSAL_PAIRS         # a sequence
    assert CAUSAL_PAIRS == 33_558_528 and core // S == 16_779_264
    assert D * VOCAB == 100_663_296
    want = 6 * STEPS * (depth * (S * application + core) + S * D * VOCAB)
    got = cell.model.flops_per_sample(config, traffic)
    assert abs(got - want) / want < 1e-12, (got, want)
    assert {12: 180.6, 8: 127.0, 6: 100.2}[depth] == round(got / 1e12, 1)
    assert cell.model.attention_flops_per_sample(config, traffic) \
        == CAUSAL_PAIRS * HEADS * STEPS * depth * 256 * 2 * 3
    exits = cell.model.exit_flops_per_sample(config, traffic)
    assert exits == STEPS * 3 * 2 * S * D * VOCAB
    # 19.79 TFLOP a sequence: 100.5 ms at 197 TFLOP/s
    assert round(exits / 1e12, 2) == 19.79
    assert round(exits / 197e12 * 1e3, 1) == 100.5
    macs = cell.model.forward_macs_per_token(config, S)
    shares = {k: round(100 * v / sum(macs.values()), 1)
              for k, v in macs.items()}
    assert shares == {
        12: {"projections": 21.9, "mlp": 45.2, "attention_cores": 21.9,
             "exits": 11.0},
        8: {"projections": 20.8, "mlp": 42.9, "attention_cores": 20.8,
            "exits": 15.6},
        6: {"projections": 19.8, "mlp": 40.7, "attention_cores": 19.8,
            "exits": 19.8}}[depth], shares


def test_rehearsal_keeps_the_loop_the_widths_ratio_and_the_kernel_routes(
        cell):
    small = cell.config["rehearsal"]["model"]
    assert small["num_hidden_layers"] == len(small["layer_types"]) >= 2
    assert "total_ut_steps" not in small        # four passes, as published
    assert cell.config["rehearsal"]["traffic"]["seq_len"] % 128 == 0
    # heads of 128 as published: `flash_causal` and the rotation kernel
    assert small["hidden_size"] // small["num_attention_heads"] == HEAD
    assert small["num_key_value_heads"] == small["num_attention_heads"]


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
    assert read({"trace": None, "samples_per_step": 1, "chips": 1,
                 "peak": None}) is None


def test_the_block_readers_are_their_siblings_over_the_new_scopes():
    """One copy of the reader's code: `mtp_device_ms`'s, whose own
    pattern stays what it was."""
    sibling = lookup._module(BENCH, "layer_metrics", "mtp_device_ms.py")
    passes = lookup._module(BENCH, "layer_metrics", "loop_pass_device_ms.py")
    exits = lookup._module(BENCH, "layer_metrics", "exit_device_ms.py")
    assert exits.read.__code__.co_filename \
        == sibling.read.__code__.co_filename
    assert sibling._IN_BLOCK.search("jit(f)/jvp(m)/mtp/layer/dot")
    assert not sibling._IN_BLOCK.search("jit(f)/jvp(m)/ut0/dot")
    in_pass = passes._reader._IN_BLOCK
    assert in_pass.search("jit(f)/jvp(m)/model/ut/while/body/layer3/dot")
    assert in_pass.search(
        "jit(f)/transpose(jvp(m))/model/ut/final/rematted_computation/mul")
    assert in_pass.search("jit(f)/jvp(ut)/dot")
    assert not in_pass.search("jit(f)/jvp(m)/model/ut0/layers/layer3/dot")
    assert not in_pass.search("jit(f)/jvp(m)/model/exit/dot")
    assert not in_pass.search("jit(f)/jvp(m)/model/utx/dot")
    assert not in_pass.search("jit(f)/jvp(m)/model/layout0/dot")
    in_exit = exits._reader._IN_BLOCK
    assert in_exit.search("jit(f)/jvp(m)/model/exit/FullyConnected/dot")
    assert in_exit.search("jit(f)/transpose(jvp(m))/exit/_log_pdf/mul")
    assert not in_exit.search("jit(f)/jvp(m)/model/ut0/layers/layer3/dot")
    assert not in_exit.search("jit(f)/jvp(m)/model/exit_loss/dot")
    assert not in_exit.search("jit(f)/jvp(m)/mtp/dot")


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
    assert set(facts["reference_rel_l2"]) == {
        "exit1", "exit2", "exit3", "exit4", "exit_pdf"}
    loop, = [i["loop"] for i in info if "loop" in i]
    small = lookup.cell(CELL).config["rehearsal"]["model"]
    layers = small["num_hidden_layers"]
    assert loop["ut_steps"] == STEPS and loop["layers_traced"] == layers
    assert loop["layer_applications"] == STEPS * layers
    assert loop["param_uses"] == loop["param_uses_expected"] == {
        "1": 1, "4": 11 * layers + 4}
    # the pass is traced once: N kernels, their residuals stacked by the scan
    assert loop["kept_residuals_a_trip"]["values"] == 2 * layers
    assert loop["kept_bytes_all_trips"] \
        == STEPS * loop["kept_residuals_a_trip"]["bytes"] > 0
    assert loop["attention_routes"]["flash_causal"] == layers
    assert loop["attention_routes"]["reference"] == 0
    assert loop["rotary_routes"] == {"kernel": 2 * layers, "xla": 0}
