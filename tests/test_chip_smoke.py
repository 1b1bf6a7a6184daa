"""chip_smoke.py and the measurement entry points, as far as the CPU can
check them: they refuse to run without a chip, the smoke's phase functions
pass at toy size (Pallas in interpret mode), the peak table knows the
device_kind the v5e reports, and the attention kernel compiles for the
v5e ahead of time (libtpu compiles without a chip; it cannot run)."""
import os
import subprocess
import sys
import types

import pytest

import chip_smoke
import mxnet_tpu as mx
from mxnet_tpu.compile_cache import jax_cache
from mxnet_tpu.telemetry.mxprof import costs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + cmd, capture_output=True,
                          text=True, cwd=_REPO, timeout=timeout, env=env)


def _json_lines(text):
    return [ln for ln in text.splitlines() if ln.startswith("{")]


def test_chip_smoke_refuses_cpu():
    p = _run(["chip_smoke.py"])
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert not _json_lines(p.stdout)


def test_result_line_has_exactly_the_contracted_keys():
    """The driver reads the last stdout line and refuses any other key:
    per-phase detail belongs on the `[summary]` line before it."""
    import json

    import jax
    out = json.loads(chip_smoke.result_line(jax.devices()))
    assert list(out) == ["ok", "device"] and out["ok"] is True
    assert list(out["device"]) == ["platform", "kind", "count"]
    assert out["device"] == {"platform": "cpu",
                             "kind": jax.devices()[0].device_kind,
                             "count": len(jax.devices())}


@pytest.mark.parametrize("script", ["bench.py", "bench_all.py"])
def test_bench_refuses_cpu(script):
    p = _run([script])
    assert p.returncode != 0
    assert "'platform': 'cpu'" in p.stderr
    assert not _json_lines(p.stdout)
    assert "last_good" not in p.stdout + p.stderr


def test_resnet_phase_toy_one_and_two_devices():
    """The dp=2 step on the base batch repeated twice sees the same
    BatchNorm statistics and mean loss as dp=1 on the base batch."""
    cache = jax_cache.JaxCache("unused")
    size = dict(image=32, warmup=2, steps=2, model="resnet18_v1",
                classes=10)
    one = chip_smoke.resnet_phase(cache, n_dev=1, batch=4, **size)
    two = chip_smoke.resnet_phase(cache, n_dev=2, batch=8, tile=2, **size)
    assert two["first_loss"] == pytest.approx(one["first_loss"], rel=2e-2)
    assert one["ms_per_step"] > 0 and two["n_dev"] == 2


def test_gluon_phase_toy():
    out = chip_smoke.gluon_phase(mx.cpu(0), batch=32, steps=5)
    assert out["tail"] == "fused" and out["last_loss"] < out["first_loss"]


def test_attention_phase_toy_interpret(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    out = chip_smoke.attention_phase(
        ((4, 128, 64, False), (2, 64, 64, True)), expect_mosaic=False,
        train_shapes=((3, 2, 128, 64),))
    assert len(out) == 3
    errs = out["train_b3_h2_s128_d64"]["max_rel_err"]
    assert set(errs) == {"o", "dq", "dk", "dv"} and max(errs.values()) < 2e-2


@pytest.mark.parametrize("dtype,n_dev", [("float32", 1), ("bfloat16", 1),
                                         ("float32", 4)])
def test_attention_train_stage_toy_interpret(monkeypatch, dtype, n_dev):
    """Value and gradients through the op's own training route, kernels in
    the interpreter, against the XLA path under the identical hash mask;
    lengths 2 and 3 and two query blocks.  `n_dev` 4: the multi-chip
    stage, four times the batch split over a dp mesh, one call a device."""
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    out = chip_smoke.attention_train_stage(((4, 2, 256, 64),), dtype=dtype,
                                           expect_mosaic=False, n_dev=n_dev)
    name = "train_b4_h2_s256_d64" if n_dev == 1 else "train_b16_h2_s256_d64_dp4"
    errs = out[name]["max_rel_err"]
    assert max(errs.values()) < (1e-4 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decoder_phase_toy(dtype):
    """The decoder stage's six comparisons at toy sizes, lowered for
    the CPU (the routes' XLA twins against the stage's plain oracles)."""
    out = chip_smoke.decoder_phase(seq=128, heads=4, window=64, tokens=64,
                                   experts=8, held=2, top_k=3, latent=16,
                                   width=24, scan=(256, 4, 64, 2, 128),
                                   dtype=dtype, expect_mosaic=False)
    assert len(out) == 6 and "ssd_scan_s256_h4_p64_g2_n128" in out
    # grouped heads over `seq`, and one head a key/value head over twice it
    assert {"causal_gqa_h4_kv2_s128_d128", "causal_gqa_h2_kv2_s256_d128"} \
        <= set(out)
    assert "window64_gqa_h16_kv2_s128_d128" in out
    assert "gated_experts_t64_held2_k16_n24" in out
    assert max(v["max_rel_err"] for v in out.values()) < (
        1e-4 if dtype == "float32" else 2e-2)


def test_decoder_phase_rejects_the_reference_in_the_kernels_place():
    with pytest.raises(AssertionError, match="no Mosaic call"):
        chip_smoke.decoder_phase(seq=128, heads=4, tokens=64, experts=8,
                                 held=2, top_k=3, latent=16, width=24)


def test_attention_train_stage_rejects_the_reference_in_the_kernels_place():
    """Lowered for the CPU the route takes the XLA reference: counted as
    `fused_train`, but no Mosaic call, and the smoke must say so."""
    with pytest.raises(AssertionError, match="no Mosaic call mx_attention"):
        chip_smoke.attention_train_stage(((3, 2, 128, 64),))


def test_attention_phase_rejects_the_reference_in_the_kernels_place():
    """On CPU without interpret mode `_attend` lowers to the XLA
    reference; the smoke must not accept that as the Mosaic kernel."""
    with pytest.raises(AssertionError, match="no Mosaic custom call"):
        chip_smoke.attention_phase(((2, 64, 64, False),))


def test_peak_table_knows_the_v5e_device_kind():
    # "TPU v5 lite" is what jax 0.9.0 / libtpu 0.0.34 reports on the v5e
    assert costs.peak_flops("TPU v5 lite") == (197e12, "table")
    assert costs.peak_flops("TPU v9 mega") == (None, "unknown")
    assert costs.peak_flops("cpu") == (None, "unknown")


def test_device_phase_unknown_accelerator_is_an_error():
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 mega")
    with pytest.raises(RuntimeError, match="_PEAK_BY_KIND"):
        chip_smoke.device_phase(fake)
    assert chip_smoke.device_phase()["platform"] == "cpu"


_AOT = r"""
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
sh = SingleDeviceSharding(topo.devices[0])
for bh, s, d, causal in ((384, 128, 64, False), (512, 64, 64, True)):
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=sh)
    fn = jax.jit(lambda q, k, v, m, c=causal: pa._attention_pallas(
        q, k, v, m, 0.125, c), in_shardings=sh, out_shardings=sh)
    fn.lower(arg(bh, s, d), arg(bh, s, d), arg(bh, s, d),
             arg(bh, s)).compile()
print("AOT_OK")
"""

# jax.grad through the op's training route at the two benchmark shapes
# (bert_base_s512: B 40; bert_base_s128: B 264; 12 heads of 64): what the
# step program holds for every layer
_AOT_TRAIN = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
sh = SingleDeviceSharding(topo.devices[0])
for b, h, s, d in ((40, 12, 512, 64), (264, 12, 128, 64)):
    arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh)
    def loss(q, k, v, m, key):
        with jax.named_scope("dot_product_attention"):
            o = pa._dot_product_attention(q, k, v, m, key, num_heads=h,
                                          dropout=0.1, _train=True)
        return o.astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg((b, s, h * d)), arg((b, s, h * d)), arg((b, s, h * d)),
        arg((b, s)), arg((2,), jnp.uint32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
    print("MOSAIC", s, len(calls), names)
    fwd = [n for n in names if "mx_attention_train_fwd" in n]
    bwd = [n for n in names if "mx_attention_train_bwd" in n]
    assert len(calls) == 2 and len(fwd) == 1 and len(bwd) == 1, names
    assert "transpose(" in bwd[0] and "transpose(" not in fwd[0], names
    assert all("dot_product_attention" in n for n in names), names
assert pa.route_counts()["fused_train"] == 2, pa.route_counts()
print("AOT_OK")
"""


# the same gradient as SPMDTrainer builds it on four chips: traced inside
# the mesh's scope, operands sharded over the batch (dp=4 of the two cells'
# batches); and on one chip over the rest of the admitted set, at the
# blocks and the VMEM request the code derives from the shape
_AOT_TRAIN_MORE = r"""
import contextlib, re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu import parallel
from mxnet_tpu.ops import pallas_attention as pa

def compiled_gradient(b, h, s, d, dtype, rows, whole, mesh=None):
    arg = lambda shape, dt=dtype, sh=rows: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh)
    def loss(q, k, v, m, key):
        with mesh or contextlib.nullcontext():
            o = pa._dot_product_attention(q, k, v, m, key, num_heads=h,
                                          dropout=0.1, _train=True)
        return o.astype(jnp.float32).sum()
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg((b, s, h * d)), arg((b, s, h * d)), arg((b, s, h * d)),
        arg((b, s)), arg((2,), jnp.uint32, whole)).compile().as_text()

mode = sys.argv[1]
if mode == "dp4":
    mesh = parallel.make_mesh({"dp": 4}, devices=topo.devices)
    rows, whole = (NamedSharding(mesh.mesh, spec) for spec in (P("dp"), P()))
    shapes = [(160, 12, 512, 64, jnp.bfloat16),
              (1056, 12, 128, 64, jnp.bfloat16)]
else:
    mesh, rows = None, SingleDeviceSharding(topo.devices[0])
    whole = rows
    shapes = [(7, 12, 384, 64, jnp.bfloat16), (16, 12, 640, 64, jnp.bfloat16),
              (16, 12, 768, 64, jnp.bfloat16), (16, 12, 896, 64, jnp.bfloat16),
              (16, 12, 1024, 64, jnp.bfloat16), (16, 8, 1024, 128, jnp.bfloat16),
              (16, 4, 1024, 256, jnp.bfloat16), (3, 2, 896, 256, jnp.float32),
              (40, 12, 512, 64, jnp.float32), (16, 8, 1024, 128, jnp.float32)]
for b, h, s, d, dtype in shapes:
    text = compiled_gradient(b, h, s, d, dtype, rows, whole, mesh)
    names = [re.search(r'op_name="([^"]*)"', ln).group(1)
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    print("MOSAIC", mode, (b, h, s, d), names)
    assert len(names) == 2, names
    assert "mx_attention_train_fwd" in names[0] + names[1], names
    assert any("mx_attention_train_bwd" in n and "transpose(" in n
               for n in names), names
    if mesh is not None:
        # each chip works on its own rows: nothing crosses chips
        assert all("shard_map" in n for n in names), names
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert op + "(" not in text, op
assert pa.route_counts()["fused_train"] == len(shapes), pa.route_counts()
print("AOT_OK")
"""


# the decoder's kernel routes at the nemotron3_super_s8192 cell's
# shapes: jax.grad through the causal grouped-query attention route (32
# query heads over 2 key/value heads of 128, S = 8192) and through the
# held experts' stage (8192 tokens, 8 experts held of a top-22 router: a
# plan of 65536 rows in chunks of ROW_CHUNK, latent 1024, expert 2688)
_AOT_DECODER = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.parallel import moe
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)

def mosaic_names(fn, *args):
    # splash's custom calls are printed over three lines: the table's
    # own reader joins them
    from mxnet_tpu.parallel.spmd import _whole_instructions
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in _whole_instructions(text)
             if 'custom_call_target="tpu_custom_call"' in ln]
    return [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]

s, h, kv, d = 8192, 32, 2, 128
def attention_loss(q, k, v):
    with jax.named_scope("dot_product_attention"):
        o = pa._dot_product_attention(q, k, v, None, None, num_heads=h,
                                      num_kv_heads=kv, causal=True,
                                      _train=True)
    return o.astype(jnp.float32).sum()
names = mosaic_names(jax.grad(attention_loss, argnums=(0, 1, 2)),
                     arg((1, s, h * d)), arg((1, s, kv * d)),
                     arg((1, s, kv * d)))
print("MOSAIC attention", names)
# forward, and ONE backward: dK, dV and dQ from each score block once
assert len(names) == 2, names
assert all("dot_product_attention" in n for n in names), names
assert [n for n in names if "transpose(" in n] == [
    n for n in names if "mx_causal_attention_bwd" in n] != [], names
assert pa.route_counts()["flash_causal"] == 1, pa.route_counts()
assert pa.backward_counts() == {"fused": 1, "band": 0, "split": 0}

t, held, top_k, latent, width = 8192, 8, 22, 1024, 2688
rows = moe.plan_rows(t, top_k, held)
assert rows == t * held
def experts_loss(u, w1, w2, token, weight, sizes):
    plan = moe.RoutePlan(token, weight, sizes, jnp.zeros((), jnp.int32))
    with jax.named_scope("moe_experts"):
        return moe.experts(u, plan, w1, w2).astype(jnp.float32).sum()
names = mosaic_names(
    jax.value_and_grad(experts_loss, argnums=(0, 1, 2, 4)), arg((t, latent)),
    arg((held, latent, width)), arg((held, width, latent)),
    arg((rows,), jnp.int32), arg((rows,), jnp.float32),
    arg((held,), jnp.int32))
print("MOSAIC experts", names)
# every call sits in one of the two loops over the plan's chunks: the
# forward's two products; in the backward's, both again (the chunk's
# hidden is not kept, and its output is what the combine weights'
# gradient is made of) and, for each, dlhs and the transposed drhs
assert all("moe_experts" in n and "/while/body/" in n for n in names), names
assert sum("transpose(" not in n for n in names) == 2, names
back = [n for n in names
        if "transpose(jvp(moe_experts))/jit(_backward)/while/body/" in n]
assert len(back) == 6 and sum("jit(tgmm)" in n for n in back) == 2, names
assert moe.route_counts()["grouped_kernel"] == 2, moe.route_counts()
# each of the six kernel calls on a tile of its own that divides its k and n
print("TILES experts", moe.tile_choices())
assert moe.route_counts()["padded_tiles"] == 0, moe.route_counts()
assert moe.route_counts()["exact_tiles"] >= 6, moe.route_counts()
assert {kind for kind, *_ in moe.tile_choices()} == set(moe.KINDS)

from mxnet_tpu.ops import ssm
s, h, p, g, n = 8192, 128, 64, 8, 128
def scan_loss(*a):
    with jax.named_scope("ssd_scan"):
        return ssm._ssd_scan(*a, chunk=128).astype(jnp.float32).sum()
vec = arg((h,), jnp.float32)
names = mosaic_names(
    jax.grad(scan_loss, argnums=tuple(range(7))), arg((1, s, h, p)),
    arg((1, s, h)), vec, arg((1, s, g, n)), arg((1, s, g, n)), vec, vec)
print("MOSAIC scan", names)
assert len(names) == 2 and all("ssd_scan" in n for n in names), names
assert sum("mx_ssd_scan_fwd" in n and "transpose(" not in n
           for n in names) == 1, names
assert sum("mx_ssd_scan_bwd" in n and "transpose(" in n
           for n in names) == 1, names
assert ssm.route_counts() == {"chunked_xla": 0, "fused_kernel": 1}
# the other widths the route admits: a head of 128 alone in its lane
# block, a head of 256, a state of two lane blocks, float32 operands
for s, h, p, g, n, dt in ((1024, 8, 128, 2, 128, jnp.bfloat16),
                          (1024, 4, 256, 1, 128, jnp.bfloat16),
                          (1024, 16, 64, 2, 256, jnp.float32)):
    names = mosaic_names(
        jax.grad(scan_loss, argnums=tuple(range(7))), arg((2, s, h, p), dt),
        arg((2, s, h), dt), arg((h,), jnp.float32), arg((2, s, g, n), dt),
        arg((2, s, g, n), dt), arg((h,), jnp.float32), arg((h,), jnp.float32))
    assert len(names) == 2, (s, h, p, g, n, names)
print("AOT_OK")
"""


def test_attention_kernel_compiles_for_v5e_ahead_of_time():
    """Mosaic runs inside libtpu's compiler, which works without a chip.
    The kernel's old one-shot probe compiled fp32 (2,128,64) and passed
    while the bf16 shapes real models use aborted the process in
    vector-layout inference; a compiler abort kills the process, hence
    the subprocess."""
    p = _run(["-c", _AOT], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, p.stderr[-3000:]


_AOT_LAGUNA = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.parallel import moe
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)

def mosaic_names(fn, *args):
    # splash's custom calls are printed over three lines: the table's
    # own reader joins them
    from mxnet_tpu.parallel.spmd import _whole_instructions
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in _whole_instructions(text)
             if 'custom_call_target="tpu_custom_call"' in ln]
    return [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]

b, s, h, kv, d, window = 2, 8192, 64, 8, 128, 512
def window_loss(q, k, v):
    with jax.named_scope("sliding_window_attention"):
        o = pa._sliding_window_attention(q, k, v, num_heads=h,
                                         num_kv_heads=kv, window=window)
    return o.astype(jnp.float32).sum()
names = mosaic_names(jax.grad(window_loss, argnums=(0, 1, 2)),
                     arg((b, s, h * d)), arg((b, s, kv * d)),
                     arg((b, s, kv * d)))
print("MOSAIC window", names)
assert len(names) == 2, names       # forward, and dQ, dK and dV in one
assert all("sliding_window_attention" in n for n in names), names
assert ["mx_window_attention_bwd" in n and "transpose(" in n
        for n in names] == [False, True], names
assert pa.route_counts()["splash_window"] == 1, pa.route_counts()
assert pa.route_counts()["flash_causal"] == 0, pa.route_counts()
assert pa.backward_counts() == {"fused": 0, "band": 1, "split": 0}

t, held, top_k, hidden, width = 16384, 32, 8, 2048, 512
rows = moe.plan_rows(t, top_k, held)
assert rows == t * top_k
expected = t * top_k * held // 256      # the model's: one chunk of 32,768
assert moe.row_chunk(expected) == 32768
def experts_loss(u, w1, w2, token, weight, sizes):
    plan = moe.RoutePlan(token, weight, sizes, jnp.zeros((), jnp.int32))
    with jax.named_scope("moe_experts"):
        return moe.experts(u, plan, w1, w2, "silu_gated",
                           expected).astype(jnp.float32).sum()
names = mosaic_names(
    jax.value_and_grad(experts_loss, argnums=(0, 1, 2, 4)), arg((t, hidden)),
    arg((held, hidden, 2 * width)), arg((held, width, hidden)),
    arg((rows,), jnp.int32), arg((rows,), jnp.float32),
    arg((held,), jnp.int32))
print("MOSAIC gated experts", names)
# as for relu^2: both loops' calls, two forward, six backward
assert all("moe_experts" in n and "/while/body/" in n for n in names), names
assert sum("transpose(" not in n for n in names) == 2, names
back = [n for n in names
        if "transpose(jvp(moe_experts))/jit(_backward)/while/body/" in n]
assert len(back) == 6 and sum("jit(tgmm)" in n for n in back) == 2, names
# 512 rows an expert expected: no kernel takes the 512-row tile that a
# group boundary cuts in two, and none pads
tiles = moe.tile_choices()
print("TILES gated experts", tiles)
assert moe.route_counts()["padded_tiles"] == 0, moe.route_counts()
assert len(tiles) == 6 and all(tm < 512 for tm, _, _ in tiles.values())
print("AOT_OK")
"""


def test_attention_training_kernels_compile_for_v5e_ahead_of_time():
    """The gradient of a BERT-base training call holds two Mosaic calls at
    both benchmark shapes: `mx_attention_train_fwd` and ONE backward,
    `mx_attention_train_bwd` (dQ, dK and dV together; ISSUE 26 counted
    three for a split backward).  The backward's `op_name` holds
    `transpose(` and the op scope, which is what `scope_time` books it by
    in a device trace."""
    p = _run(["-c", _AOT_TRAIN], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


@pytest.mark.parametrize("mode", ["dp4", "one_chip_admitted_shapes"])
def test_attention_training_route_compiles_beyond_the_benchmark_shapes(mode):
    """`dp4`: GSPMD cannot partition a Mosaic call ("Mosaic kernels cannot
    be automatically partitioned"), so a BERT step on dp > 1 builds only
    because the route wraps its kernels in a shard_map over the batch
    axes; compiled for the v5e 2x2 it holds the two calls a chip and no
    collective.  `one_chip_admitted_shapes`: S a 512-row block does not
    divide, S=1024, 128- and 256-wide heads and f32 operands fit the VMEM
    the code asks for."""
    p = _run(["-c", _AOT_TRAIN_MORE, mode], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


def test_window_and_gated_expert_routes_compile_for_v5e_ahead_of_time():
    """Sliding-window grouped-query attention through the splash route
    (upstream's forward and `mx_window_attention_bwd`) and the silu-gated expert stage through the
    grouped-matmul kernel, at `laguna_xs2_s8192`'s shapes: Mosaic takes
    them, and every call keeps its op scope and, in the backward,
    `transpose(`: what `window_attention_device_ms` and
    `gated_moe_device_ms` are read by."""
    p = _run(["-c", _AOT_LAGUNA], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


def test_decoder_kernel_routes_compile_for_v5e_ahead_of_time():
    """Causal grouped-query training attention through the O(S)
    `flash_causal` route (upstream's splash multi-query forward kernel
    and `mx_causal_attention_bwd`, sixteen query heads a key/value
    head), the expert layer's grouped products
    through the grouped-
    matmul kernel, and the Mamba-2 scan through its forward and backward
    kernels, at the hybrid decoder cell's shapes: Mosaic takes them, and
    every call keeps its op scope and, in the backward, `transpose(`:
    what `causal_attention_device_ms`, `moe_device_ms` and
    `ssd_device_ms` are read by."""
    p = _run(["-c", _AOT_DECODER], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


_AOT_EVA = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel.spmd import _whole_instructions
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)

b, s, h, d, window, chunk = 1, 32768, 32, 128, 2048, 16
def loss(q, k, v, phi, mu):
    ks, vs = apply_pure("eva_chunk_summary", k, v, phi, mu, num_heads=h,
                        chunk=chunk)
    o = apply_pure("eva_attention", q, k, v, ks, vs, num_heads=h,
                   window=window, chunk=chunk)
    return o.astype(jnp.float32).sum()
wide = arg((b, s, h * d))
compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
    wide, wide, wide, arg((h, d)), arg((h, d))).compile()
calls = [ln for ln in _whole_instructions(compiled.as_text())
         if 'custom_call_target="tpu_custom_call"' in ln]
names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
print("MOSAIC eva", names)
assert len(names) == 3, names       # forward, dK/dV, dQ
assert all("eva_attention" in n for n in names), names
assert sum("transpose(" in n for n in names) == 2, names
assert pa.route_counts()["eva_splash"] == 1, pa.route_counts()
assert pa.route_counts()["eva_xla"] == 0, pa.route_counts()
# O(S x (W + S / C)): a dense (32, 32768, 34816) score would be 68 GiB
assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
print("AOT_OK")
"""


def test_eva_routes_compile_for_v5e_ahead_of_time():
    """`eva_chunk_summary` and `eva_attention` at `evabyte_s32768`'s
    shapes (32 heads of 128, 32,768 positions, window 2048, chunk 16),
    value and every gradient: Mosaic takes the splash kernels over
    [keys ; summaries] under the mask computed in the kernel (forward,
    dK/dV, dQ), every call keeps the op scope and, in the backward,
    `transpose(`: what `eva_attention_device_ms` is read by."""
    p = _run(["-c", _AOT_EVA], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


_AOT_LATENT = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel.spmd import _whole_instructions
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)

b, s, h, nope, rope, vd = 2, 8192, 32, 128, 64, 128
def loss(q, k_nope, k_rope, v):
    o = apply_pure("latent_attention", q, k_nope, k_rope, v, num_heads=h)
    return o.astype(jnp.float32).sum()
compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
    arg((b, s, h * (nope + rope))), arg((b, s, h * nope)),
    arg((b, s, rope)), arg((b, s, h * vd))).compile()
calls = [ln for ln in _whole_instructions(compiled.as_text())
         if 'custom_call_target="tpu_custom_call"' in ln]
names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
print("MOSAIC latent", names)
# forward, and ONE backward: dK, dV and dQ from each score block once
assert len(names) == 2, names
assert all("latent_attention" in n for n in names), names
assert [n for n in names if "transpose(" in n] == [
    n for n in names if "mx_causal_attention_bwd" in n] != [], names
assert pa.backward_counts() == {"fused": 1, "band": 0, "split": 0}
assert pa.route_counts()["latent_splash"] == 1, pa.route_counts()
assert pa.route_counts()["latent_xla"] == 0, pa.route_counts()
# O(S): dense (2, 32, 8192, 8192) float32 scores would be 16 GiB
assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
print("AOT_OK")
"""


def test_latent_attention_route_compiles_for_v5e_ahead_of_time():
    """`latent_attention` at `joyai_llm_flash_s8192`'s shapes (2 x 8192
    positions, 32 heads of 192 for queries and keys, 64 of every key one
    shared vector, and of 128 for values), value and the four gradients:
    Mosaic takes upstream's forward kernel at a value size of its own
    and `mx_causal_attention_bwd` at heads of 192 (dQ's blocks in 256
    lanes), every call keeps the op scope and, in the backward,
    `transpose(`: what `mla_attention_device_ms` is read by."""
    p = _run(["-c", _AOT_LATENT], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


_AOT_LFM2 = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import rotary
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel.spmd import _whole_instructions
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)

def mosaic(compiled):
    calls = [ln for ln in _whole_instructions(compiled.as_text())
             if 'custom_call_target="tpu_custom_call"' in ln]
    return [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]

b, s, h, kv, d = 2, 8192, 32, 8, 64
cos = arg((s, d), jnp.float32)
def loss(q, k, v, cos, sin):
    q, k = apply_pure("rotary_embedding", q, k, cos, sin, num_heads=h,
                      num_kv_heads=kv)
    o = apply_pure("dot_product_attention", q, k, v, None, causal=True,
                   num_heads=h, num_kv_heads=kv)
    return o.astype(jnp.float32).sum()
before, turned = pa.route_counts(), rotary.route_counts()
compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
    arg((b, s, h * d)), arg((b, s, kv * d)), arg((b, s, kv * d)),
    cos, cos).compile()
names = mosaic(compiled)
print("MOSAIC lfm2", names)
cores = [n for n in names if "dot_product_attention" in n]
turns = [n for n in names if "rotary_embedding" in n]
# forward, and ONE backward: dK, dV and dQ from each score block once
assert len(cores) == 2, names
assert [n for n in cores if "transpose(" in n] == [
    n for n in cores if "mx_causal_attention_bwd" in n] != [], names
assert len(turns) == 4 and all("mx_rotary_turn" in n for n in turns), names
after = pa.route_counts()
assert after["flash_causal"] == before["flash_causal"] + 1, after
assert after["reference"] == before["reference"], after
assert after["kernel_infer"] == before["kernel_infer"], after
assert rotary.route_counts() == {"kernel": turned["kernel"] + 2,
                                 "xla": turned["xla"]}
# O(S): dense (2, 32, 8192, 8192) float32 scores would be 16 GiB
assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30

dm = 2048
conv = jax.jit(lambda x, w: apply_pure("short_conv", x, w)).lower(
    arg((b, s, 3 * dm)), arg((dm, 3))).compile()
# one pass: nothing of a stream's size between the operands and y
assert conv.memory_analysis().temp_size_in_bytes < 2 ** 20, \
    conv.memory_analysis().temp_size_in_bytes
assert not mosaic(conv)
both = jax.jit(lambda x, w, g: jax.vjp(
    lambda x, w: apply_pure("short_conv", x, w), x, w)[1](g)).lower(
    arg((b, s, 3 * dm)), arg((dm, 3)), arg((b, s, dm))).compile()
# the backward lags ONE float32 stream, z (128 MiB), beside a bfloat16 one
assert both.memory_analysis().temp_size_in_bytes <= 200 * 2 ** 20, \
    both.memory_analysis().temp_size_in_bytes
print("AOT_OK")
"""


def test_lfm2_routes_compile_for_v5e_ahead_of_time():
    """`lfm2_8b_a1b_s8192`'s attention layer at its shapes (2 x 8192
    positions, 32 query heads over 8 key/value heads of 64): the
    rotation takes the kernel at d = 64 (four passes: q and k, forward
    and backward) and the causal core upstream's forward kernel and
    `mx_causal_attention_bwd` at 64-lane operands, every call under its op scope
    and, in the backward, `transpose(`: what `head64_attention_device_ms`
    is read by; nothing of S x S is planned.  `short_conv` at the cell's
    streams is plain XLA that plans no temporary in its forward pass and
    one float32 stream in its backward."""
    p = _run(["-c", _AOT_LFM2], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


_AOT_ROTARY = r"""
import re, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import rotary
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel.spmd import _whole_instructions
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)

# cell's layer kind: B, S, heads, kv heads, D, Dk, r, pairing
for name, b, s, h, kv, d, dk, r, pairing in (
        ("laguna_window", 2, 8192, 64, 8, 128, 128, 128, {}),
        ("laguna_full", 2, 8192, 48, 8, 128, 128, 64, {}),
        ("evabyte", 1, 32768, 32, 32, 128, 128, 128, {}),
        ("joyai", 2, 8192, 32, 1, 192, 64, 64,
         dict(interleaved=True, rotate_last=True))):
    def turned(q, k, cos, sin):
        return apply_pure("rotary_embedding", q, k, cos, sin, num_heads=h,
                          num_kv_heads=kv, **pairing)
    def both(q, k, cos, sin, gq, gk):
        out, vjp = jax.vjp(lambda q, k: turned(q, k, cos, sin), q, k)
        return out, vjp((gq, gk))
    before = rotary.route_counts()
    q, k = arg((b, s, h * d)), arg((b, s, kv * dk))
    table = arg((s, r), jnp.float32)
    compiled = jax.jit(both).lower(q, k, table, table, q, k).compile()
    routes = {key: n - before[key]
              for key, n in rotary.route_counts().items()}
    assert routes == {"kernel": 2, "xla": 0}, (name, routes)
    calls = [ln for ln in _whole_instructions(compiled.as_text())
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in calls]
    print("MOSAIC", name, names)
    # q and k, forward and backward: one pass each
    assert len(names) == 4, (name, names)
    assert all("rotary_embedding" in n and "mx_rotary_turn" in n
               for n in names), (name, names)
    assert sum("transpose(" in n for n in names) == 2, (name, names)
    # nothing of q's size in float32 reaches HBM
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    wide = [m for m in re.findall(r"f32\[([\d,]+)\]", entry)
            if np.prod([int(n) for n in m.split(",")]) >= b * s * h * d]
    assert not wide, (name, wide[:3])
print("AOT_OK")
"""


def test_rotary_kernel_compiles_for_v5e_ahead_of_time():
    """`rotary_embedding` at the shapes of the three decoder cells' four
    layer kinds, value and gradient of query and key under cotangents of
    their own: Mosaic takes the rotation kernel for all four pairings
    (whole head, part of it, interleaved on the last 64 of 192 in place
    over the second lane tile, the one shared key of 64), every operand takes the `kernel`
    route, every call keeps the op scope (`rotary_device_ms` reads it)
    and, in the backward, `transpose(`; and no float32 array as large
    as the queries is written to HBM (the XLA form writes two)."""
    p = _run(["-c", _AOT_ROTARY], timeout=300)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]


_AOT_PHI4 = r"""
import re, sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO_TPU_COMPILER", type(e).__name__, e)
    sys.exit(0)
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import selective_scan as ss
from mxnet_tpu.ops.registry import apply_pure
from mxnet_tpu.parallel.spmd import _whole_instructions
sh = SingleDeviceSharding(topo.devices[0])
arg = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
    shape, dt, sharding=sh)
f32 = jnp.float32

def mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [ln for ln in _whole_instructions(compiled.as_text())
             if 'custom_call_target="tpu_custom_call"' in ln]
    return compiled, [re.search(r'op_name="([^"]*)"', ln).group(1)
                      for ln in calls]

b, s, d, n = 1, 16384, 5120, 16
def scan_loss(*a):
    return apply_pure("selective_scan", *a).astype(f32).sum()
compiled, names = mosaic(
    jax.grad(scan_loss, argnums=tuple(range(7))),
    arg((b, s, d)), arg((b, s, d)), arg((d, n), f32), arg((b, s, n)),
    arg((b, s, n)), arg((d,), f32), arg((d,), f32))
print("MOSAIC scan", names)
assert len(names) == 2 and all("selective_scan" in x for x in names), names
assert sum("mx_selective_scan_fwd" in x and "transpose(" not in x
           for x in names) == 1, names
assert sum("mx_selective_scan_bwd" in x and "transpose(" in x
           for x in names) == 1, names
assert ss.route_counts() == {"chunked_xla": 0, "fused_kernel": 1}
# the (B, S, D, N) float32 states would be 5 GiB: 1 / 64 of them is kept
assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30

h, kv, hd = 40, 20, 64
def core_loss(window):
    def loss(q, k, v, *small):
        return apply_pure("differential_attention", q, k, v, *small,
                          num_heads=h, num_kv_heads=kv, window=window,
                          lambda_init=0.5).astype(f32).sum()
    return jax.grad(loss, argnums=tuple(range(8)))
small = [arg((hd,), f32)] * 4 + [arg((2 * hd,), f32)]
for window, scope, kernel in ((0, "full", "mx_causal_attention_bwd"),
                              (512, "window", "mx_window_attention_bwd")):
    compiled, names = mosaic(core_loss(window), arg((b, s, h * hd)),
                             arg((b, s, kv * hd)), arg((b, s, kv * hd)),
                             *small)
    print("MOSAIC differential", window, names)
    assert len(names) == 2, names
    # `jvp(` / `transpose(` wrap the outermost scope, here the op's
    assert all(re.search(rf"differential_attention\)*/{scope}/", x)
               for x in names), names
    backward = [x for x in names if "transpose(" in x]
    assert len(backward) == 1 and kernel in backward[0], names
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30
assert pa.backward_counts() == {"fused": 1, "band": 1, "split": 0}
counts = pa.route_counts()
assert counts["diff_splash"] == counts["diff_window_splash"] == 1, counts
assert counts["diff_xla"] == 0, counts
print("AOT_OK")
"""


def test_phi4flash_kernels_compile_for_v5e_ahead_of_time():
    """`phi4_mini_flash_s16384`'s kernels at its shapes (1 x 16,384
    positions), value and every gradient: Mosaic takes the two
    selective-scan kernels at 5,120 channels of 16 states (what
    `selective_scan_device_ms` is read by: the op scope, `transpose(` in
    the backward), and the causal and the window splash route at 40
    query heads over 20 of 64 with values of 128 (the full cores'
    backward the one kernel `mx_causal_attention_bwd`, the window's the
    one kernel `mx_window_attention_bwd`), under
    `differential_attention/full` and `/window`."""
    p = _run(["-c", _AOT_PHI4], timeout=600)
    if "NO_TPU_COMPILER" in p.stdout:
        pytest.skip(p.stdout.strip()[:200])
    assert p.returncode == 0 and "AOT_OK" in p.stdout, \
        p.stdout[-2000:] + p.stderr[-3000:]
