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
        ((4, 128, 64, False), (2, 64, 64, True)), expect_mosaic=False)
    assert len(out) == 2


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
