"""The configuration of Mamba-1 scans, differential attention and a
cross-decoder that reads one memory and one key/value set, and its cell
(PR 49), on the CPU: `python -m pytest benchmark/tests -q`.  Nothing
here measures anything, and nothing here pins where the accepted entries
of BENCHMARK.json stand or how many there are."""
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

CELL = "phi4_mini_flash_s16384"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW_METRICS = {"selective_scan_device_ms": "kernels",
               "selective_scan_roofline_pct": "kernels",
               "mamba_block_device_ms": "models",
               "gmu_device_ms": "models",
               "diff_attention_device_ms": "kernels",
               "diff_attention_roofline_pct": "kernels",
               "diff_window_attention_device_ms": "kernels",
               "phi4_fwd_device_ms": "models",
               "phi4_bwd_device_ms": "models",
               "phi4_optimizer_device_ms": "optimizer",
               "phi4_scope_unattributed_pct": "device",
               "phi4_host_dispatch_ms": "one-program step, host side"}
# the accepted metrics whose readers the twins above import
TWINS = {"phi4_fwd_device_ms": "fwd_device_ms",
         "phi4_bwd_device_ms": "bwd_device_ms",
         "phi4_optimizer_device_ms": "optimizer_device_ms",
         "phi4_scope_unattributed_pct": "scope_unattributed_pct",
         "phi4_host_dispatch_ms": "host_dispatch_ms"}

# the `config` of the catalog's row for
# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
HELD = {"num_hidden_layers": 8, "vocab_size": 25008}

# by hand, from the widths above and Mamba-1's sizes (config.json's
# `assumed`)
D, INNER, N, K, R = 2560, 5120, 16, 4, 160
HEADS, KV, HEAD, MLP_WIDTH, S, WINDOW = 40, 20, 64, 10240, 16384, 512
MLP = 3 * D * MLP_WIDTH
NORMS = 2 * 2 * D                           # two LayerNorms: gain and bias
MAMBA_MIXER = (D * 2 * INNER + INNER * K + INNER + INNER * (R + 2 * N)
               + R * INNER + INNER + INNER * N + INNER + INNER * D)
LAMBDAS = 4 * HEAD + 2 * HEAD               # four vectors, the sub-norm
ATTENTION_MIXER = 2 * D * D + 2 * D * KV * HEAD + LAMBDAS
CROSS_MIXER = 2 * D * D + LAMBDAS
GMU_MIXER = 2 * D * INNER
LAYER = {"mamba": MAMBA_MIXER + MLP + NORMS,
         "attention": ATTENTION_MIXER + MLP + NORMS,
         "gmu": GMU_MIXER + MLP + NORMS, "cross": CROSS_MIXER + MLP + NORMS}


def counted(mamba, attention, gmu, cross, vocab):
    return (mamba * LAYER["mamba"] + attention * LAYER["attention"]
            + gmu * LAYER["gmu"] + cross * LAYER["cross"] + vocab * D
            + 2 * D)


@pytest.fixture(scope="module")
def cell():
    return lookup.cell(CELL)


def test_the_files_are_found_by_name(cell):
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "phi4_mini_flash", "s16384_lm_b1", 1)
    assert cell.traffic["batch"] == 1 and cell.traffic["seq_len"] == S
    entry, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    spec = json.load(open(os.path.join(BENCH, "cells", CELL + ".json")))
    assert entry["why"] == spec["why"] and len(spec["why"]) <= 200
    config, = [c for c in MANIFEST["configs"]
               if c["name"] == "phi4_mini_flash"]
    assert config["source"] == cell.config["source"]
    assert config["reduced"] == cell.config["reduced"] == list(HELD)
    assert "mxnet_tpu" not in open(os.path.join(
        BENCH, "configs", "phi4_mini_flash", "reference.py")).read().replace(
            "nothing of\nmxnet_tpu", "")


def test_every_published_key_is_there_and_only_the_cut_differs(cell):
    for key, value in PUBLISHED.items():
        want = HELD.get(key, value)
        assert cell.config[key] == want, key
        assert type(cell.config[key]) is type(want), key
    assert cell.config["published"]["num_hidden_layers"] == 32
    assert cell.config["published"]["vocab_size"] == 200064
    assert cell.config["vocab_size"] * 8 == 200064      # an eighth
    # no width is among what is cut
    assert not any(k.endswith(("_size", "_dim", "_rank")) and k != "vocab_size"
                   for k in cell.config["reduced"])
    assert (cell.config["d_state"], cell.config["d_conv"],
            cell.config["expand"], cell.config["dt_rank"]) == (N, K, 2, R)
    assert {"state_space_sizes", "layer_rule", "differential_attention",
            "layer_norm", "no_attention_bias", "no_positions", "init",
            "dtype", "optimizer", "data", "remat"} <= set(
                cell.config["assumed"])
    tol = cell.config["reference_check"]
    assert 0 < tol["scan_rel_l2_tol"] < tol["logits_rel_l2_tol"] < 1


def test_both_parameter_counts_are_the_issues_and_the_built_models(cell):
    assert LAYER == {"mamba": 119895040, "attention": 98314624,
                     "gmu": 104867840, "cross": 91761024}
    assert counted(9, 9, 7, 7, 200064) == 3852457984 \
        == cell.config["published"]["parameters_count"]
    assert counted(3, 3, 1, 1, 25008) == 915283456 \
        == cell.config["parameters_held"]
    assert cell.model.layer_counts(cell.config) == {
        "mamba": 3, "window": 2, "full": 1, "gmu": 1, "cross": 1}
    assert cell.model.layer_counts(
        dict(cell.config, num_hidden_layers=32)) == {
            "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    # the built model, at the rehearsal's widths: the same formula
    import mxnet_tpu as mx

    small = dict(cell.config, **cell.config["rehearsal"]["model"])
    step = cell.model._step_block(small)
    step.initialize(mx.initializer.Normal(0.02), ctx=mx.cpu())
    built = sum(int(p.data().size)
                for p in step.collect_params().values())
    d, inner, rank = 512, 1024, 32
    mlp, norms, lambdas = 3 * d * 768, 4 * d, 6 * 64
    mamba = (d * 2 * inner + inner * 4 + inner + inner * (rank + 32)
             + rank * inner + inner + inner * 16 + inner + inner * d)
    assert built == (
        3 * mamba + 3 * (2 * d * d + 2 * d * 4 * 64 + lambdas)
        + 2 * d * inner + 2 * d * d + lambdas + 8 * (mlp + norms)
        + 512 * d + 2 * d)


def test_flops_and_bytes_are_counted_by_hand(cell):
    model, config, traffic = cell.model, cell.config, cell.traffic
    pairs = S * (S + 1) // 2
    banded = WINDOW * (WINDOW + 1) // 2 + (S - WINDOW) * WINDOW
    assert model._visible_pairs(S) == pairs
    assert model._visible_pairs(S, WINDOW) == banded
    assert model._visible_pairs(256, 512) == 256 * 257 // 2
    macs = model.forward_macs_per_token(config, S)
    assert macs == {
        "mamba_projections": 3 * (D * 2 * INNER + INNER * (R + 2 * N)
                                  + R * INNER + INNER * D),
        "attention_projections": 3 * (2 * D * D + 2 * D * KV * HEAD)
        + 2 * D * D,
        "gmu_projections": 2 * D * INNER,
        "full_cores": 2 * HEADS * (HEAD + 2 * HEAD) * pairs / S,
        "window_cores": 2 * HEADS * (HEAD + 2 * HEAD) * banded / S,
        "mlp": 8 * MLP, "head": D * 25008}
    scan = 3 * 6 * INNER * N
    assert model.scan_flops_per_token(config) == scan
    total = 3 * (2 * sum(macs.values()) + scan) * S
    assert model.flops_per_sample(config, traffic) == total
    assert 102e12 < total < 104e12              # the issue's ~103 TFLOP
    cores = model.diff_attention_flops_per_sample(config, traffic)
    assert cores == 3 * 2 * 2 * HEADS * 3 * HEAD * pairs
    assert 0.11 < cores / total < 0.13          # ~12% of the step
    assert 3 * 2 * macs["window_cores"] * S / total < 0.01
    assert 0.05 < 3 * 2 * macs["head"] * S / total < 0.07
    nbytes = model.selective_scan_bytes_per_sample(config, traffic)
    assert nbytes == 3 * ((8 * S * INNER + 6 * S * N) * 2
                          + 3 * INNER * (N + 2) * 4)
    # under 5 ms a step at 819 GB/s: the op's floor is not its bytes
    assert nbytes / 819e9 < 0.005


def test_rehearsal_keeps_every_kind_and_the_kernel_routes(cell):
    small = dict(cell.config, **cell.config["rehearsal"]["model"])
    assert cell.model.layer_counts(small) == {
        "mamba": 3, "window": 2, "full": 1, "gmu": 1, "cross": 1}
    seq_len = cell.config["rehearsal"]["traffic"]["seq_len"]
    assert seq_len % 128 == 0 and small["sliding_window"] < seq_len
    # heads of 64 in pairs and whole (8, 128) channel registers, as
    # published: the splash routes and the scan's kernel route
    assert small["hidden_size"] // small["num_attention_heads"] == HEAD
    assert small["num_attention_heads"] // small["num_key_value_heads"] == 2
    assert small["expand"] * small["hidden_size"] % 1024 == 0


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


def test_the_block_readers_are_their_sibling_over_other_scopes():
    """One copy of the reader's code: `mtp_device_ms`'s, whose own
    pattern stays what it was."""
    sibling = lookup._module(BENCH, "layer_metrics", "mtp_device_ms.py")
    path = "jit(f)/transpose(jvp(m))/layer4/checkpoint/"
    seen = {
        "mamba_block_device_ms": path + "mamba/selective_scan/mul",
        "gmu_device_ms": path + "gmu/FullyConnected/dot_general",
        "diff_attention_device_ms":
            path + "differential_attention/full/jit(f)/pallas_call",
        "diff_window_attention_device_ms":
            path + "differential_attention/window/jit(f)/pallas_call"}
    for name, mine in seen.items():
        block = lookup._module(BENCH, "layer_metrics", name + ".py")
        assert block.read.__code__.co_filename \
            == sibling.read.__code__.co_filename
        pattern = block._reader._IN_BLOCK
        assert pattern.search(mine), name
        for other_name, other in seen.items():
            assert other_name == name or not pattern.search(other), name
        assert not pattern.search(
            path + "differential_attention/RMSNorm/mul"), name
    # `jvp(` and `transpose(` close after the component they wrap
    full = lookup._module(BENCH, "layer_metrics",
                          "diff_attention_device_ms.py")._reader._IN_BLOCK
    assert full.search("jit(f)/transpose(jvp(differential_attention))/full/"
                       "mx_causal_attention_bwd/pallas_call")
    assert sibling._IN_BLOCK.search("jit(f)/jvp(m)/mtp/layer/dot")
    assert not sibling._IN_BLOCK.search("jit(f)/jvp(m)/mamba/dot")


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
    assert set(facts["reference_rel_l2"]) == {"lm", "memory", "scan"}
    # the scan alone, under its own tolerance, stretched to run.py's one
    scan, = [i["scan_rel_l2"] for i in info if "scan_rel_l2" in i]
    tol = lookup.cell(CELL).config["reference_check"]
    assert scan <= tol["scan_rel_l2_tol"]
    assert abs(facts["reference_rel_l2"]["scan"] - scan
               * tol["logits_rel_l2_tol"] / tol["scan_rel_l2_tol"]) \
        < 0.02 * facts["reference_rel_l2"]["scan"]
    routes, = [i["routes"] for i in info if "routes" in i]
    assert routes["selective_scan"] == {"chunked_xla": 0, "fused_kernel": 3}
    assert routes["attention"] == {"diff_splash": 2, "diff_window_splash": 2}
    step, = [i["step_counters"] for i in info if "step_counters" in i]
    assert step["attention_backward"] == {"fused": 0, "split": 4}
    assert set(step["kept_residuals"]) == {"diff_splash",
                                           "diff_window_splash"}
