"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process, from the root of a copy of the repo (no git, no network),
on a machine with at least one TPU chip.  It drives the main path once
through the entry points a user calls, at the full width of ResNet-50:

  1. device     the chip, the versions, the peak table, the compile cache
  2. resnet50   vision.resnet50_v1 (NHWC, bf16, 224^2, batch 256) through
                parallel.make_mesh(dp=1) -> parallel.SPMDTrainer.step
  3. gluon      the README's front door: initialize(ctx=mx.tpu(0)),
                hybridize(), gluon.Trainer, record/backward/step
  4. attention  ops/pallas_attention._attend compiled by Mosaic, against
                the XLA reference, at BERT-base and NMT head shapes; then
                the training route (dropout on the probabilities): value
                and gradients of the fused kernels against the XLA path
                under the identical hash mask, at the benchmark's shapes
  5. multichip  (only when more than one chip is visible) the step of 2
                under make_mesh(dp=n) at batch 256*n

No phase failure is caught: any exception is a non-zero exit with no
result line.  Without an accelerator it says what it found and exits 2 -
there is no CPU run under this script's name.  The last line of stdout is
one JSON object with exactly these keys, the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it, `[summary] {...}`, is JSON too: versions, per-phase
`ok` and seconds, total seconds.

The phases are functions of a size so tests/test_chip_smoke.py can run
them at toy size on the CPU backend (Pallas in interpret mode); `main`
runs only the full size.  Any img/s printed here is information for the
reader, not a benchmark metric.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

# BERT-base heads (batch 32 x 12 heads, D=64) at S=128 and S=512 with a
# validity mask; transformer-base NMT heads (batch 64 x 8 heads, D=64)
# at S=64, causal.  (batch*heads, seq, head_dim, causal)
ATTENTION_SHAPES = ((384, 128, 64, False), (384, 512, 64, False),
                    (512, 64, 64, True))
# the two BERT-base benchmark cells: (batch, heads, seq, head_dim)
ATTENTION_TRAIN_SHAPES = ((40, 12, 512, 64), (264, 12, 128, 64))


def _require(cond, message) -> None:
    """A check that survives `python -O`, unlike `assert`."""
    if not cond:
        raise AssertionError(message)


def _inner_jaxprs(eqn):
    for value in eqn.params.values():
        for j in value if isinstance(value, (tuple, list)) else (value,):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _holds(jaxpr, primitive: str, inside: str = "") -> bool:
    """Whether `jaxpr` holds an equation of `primitive`, at any depth;
    with `inside`, one somewhere under an equation of that primitive."""
    return any(
        (not inside and e.primitive.name == primitive)
        or any(_holds(j, primitive,
                      "" if e.primitive.name == inside else inside)
               for j in _inner_jaxprs(e))
        for e in jaxpr.eqns)


def device_phase(dev=None) -> dict:
    """What JAX sees (`dev` defaults to its first device), and that the
    peak table knows this device_kind."""
    import jax
    import jaxlib

    from mxnet_tpu.telemetry.mxprof import costs

    dev = dev or jax.devices()[0]
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    peak, source = costs.peak_flops(dev.device_kind)
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
            "peak_flops": peak, "peak_source": source}
    print(f"[device] {info}", flush=True)
    if dev.platform != "cpu" and source != "table":
        raise RuntimeError(
            f"device_kind {dev.device_kind!r} is not in "
            "telemetry/mxprof/costs.py:_PEAK_BY_KIND: every MFU on this "
            "device would be null")
    return info


def resnet_phase(cache, *, n_dev=1, batch=256, image=224, warmup=3,
                 steps=10, dtype="bfloat16", model="resnet50_v1",
                 classes=1000, tile=1) -> dict:
    """bench.py's construction (the one examples/imagenet_train.py uses):
    initialise on cpu(), place onto a dp mesh, run SPMDTrainer.step on a
    fixed batch.  `tile` repeats a batch/tile base batch `tile` times, so
    that BatchNorm statistics and the mean loss of the first step equal
    those of the base batch on one device."""
    import jax

    import bench
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.spmd import step_compile_stats

    platform = jax.devices()[0].platform
    np.random.seed(0)
    mx.random.seed(0)
    before = step_compile_stats()
    jax_before = cache.counts()
    trainer = bench.resnet_trainer(model=model, classes=classes,
                                   dtype=dtype, n_dev=n_dev)

    rng = np.random.RandomState(0)
    base = batch // tile
    images = np.tile(rng.rand(base, image, image, 3).astype(dtype),
                     (tile, 1, 1, 1))
    labels = np.tile(rng.randint(0, classes, size=(base,)).astype(np.int32),
                     tile)

    # the batch lives on the device, split over dp by shard_batch
    images = trainer._place(images, None)
    labels = trainer._place(labels, None)
    shard_devices = {s.device for s in images.addressable_shards}
    _require(len(shard_devices) == n_dev and all(
        s.data.shape[0] == batch // n_dev
        for s in images.addressable_shards),
        f"batch not split {n_dev} ways: {images.sharding}")
    for name, v in trainer.params.items():
        devs = {s.device for s in v.addressable_shards}
        _require(len(devs) == n_dev and all(
            d.platform == platform for d in devs),
            f"{name} lives on {devs}, wanted {n_dev} {platform} devices")

    donated = next(iter(trainer.params.values()))
    t0 = time.perf_counter()
    loss = trainer.step(images, labels)
    first_loss = float(loss.asnumpy())
    compile_s = time.perf_counter() - t0
    _require(donated.is_deleted(),
             "the step did not donate its parameter buffers")
    for _ in range(warmup - 1):
        loss = trainer.step(images, labels)
    loss.asnumpy()
    warm = step_compile_stats()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(images, labels)
    last_loss = float(loss.asnumpy())   # blocks: the whole chain ran
    dt = time.perf_counter() - t0

    after = step_compile_stats()
    jax_after = cache.counts()
    built = (warm["count"] + warm["cache_loads"]
             - before["count"] - before["cache_loads"])
    _require(built == 1, f"{built} step programs built during warm-up")
    _require(after["count"] + after["cache_loads"]
             == warm["count"] + warm["cache_loads"],
             "a step program was built inside the timed window")
    _require(np.isfinite(first_loss) and np.isfinite(last_loss),
             f"non-finite loss: {first_loss} -> {last_loss}")
    _require(abs(last_loss - first_loss) > 1e-3,
             f"loss did not move on a fixed batch: {first_loss} -> "
             f"{last_loss}")
    for name, v in trainer.params.items():
        _require(all(d.platform == platform for d in v.devices()),
                 f"{name} left the {platform} devices: {v.devices()}")

    hits = jax_after["hits"] - jax_before["hits"]
    misses = jax_after["misses"] - jax_before["misses"]
    stats = jax.devices()[0].memory_stats()
    program = trainer.step_executable().memory_analysis()
    out = {
        "n_dev": n_dev, "batch": batch, "first_loss": first_loss,
        "last_loss": last_loss,
        "first_step_s": round(compile_s, 2),
        "step_compile_s": round(after["seconds_total"]
                                - before["seconds_total"], 2),
        "jax_cache": "hit" if hits and not misses else "miss",
        "jax_cache_counts": {"hits": hits, "misses": misses},
        "ms_per_step": round(1000 * dt / steps, 2),
        "img_per_s": round(batch * steps / dt, 1),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use")
        if stats else "memory_stats() not reported by this backend",
        # what the compiler planned for the step program, to read the
        # allocator's peak against
        "step_program_bytes": {
            "arguments": program.argument_size_in_bytes,
            "temporaries": program.temp_size_in_bytes},
    }
    print(f"[resnet dp={n_dev}] {out}", flush=True)
    return out


def gluon_phase(ctx, *, batch=512, steps=20) -> dict:
    """The README's front door on the 784-128-64-10 MLP: parameters
    initialised on `ctx`, hybridize, gluon.Trainer, record/backward/step."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon import Trainer, loss, nn
    from mxnet_tpu.optimizer import fused

    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"),
            nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    net.hybridize()
    trainer = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-3})
    loss_fn = loss.SoftmaxCrossEntropyLoss()

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(batch, 784).astype(np.float32), ctx=ctx)
    y = nd.array(rng.randint(0, 10, (batch,)).astype(np.float32), ctx=ctx)

    fused_before = fused.compile_stats()
    losses, donated = [], None
    t0 = time.perf_counter()
    for i in range(steps):
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        if i == 1:  # past the first step's deferred initialisation
            donated = next(iter(net.collect_params().values())).data().data
        trainer.step(batch)
        losses.append(float(l.mean().asnumpy()))
    dt = time.perf_counter() - t0

    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    for name, p in net.collect_params().items():
        _require(p.data().ctx == ctx and p.grad().ctx == ctx,
                 f"{name}: data on {p.data().ctx}, grad on {p.grad().ctx}")
        _require(ctx.jax_device in p.data().data.devices(),
                 f"{name} is not on {ctx.jax_device}")

    fused_built = (fused.compile_stats()["count"]
                   - fused_before["count"])
    if trainer._spmd_updater is not None:
        tail = "spmd"
    elif fused_built and trainer._fuse_update_ok:
        tail = "fused"
    else:
        tail = "eager"
    if tail == "fused":
        # optimizer/fused.py donates the weights wherever the device is
        # not the CPU, which tier-1 therefore never runs
        _require(donated.is_deleted() == (ctx.jax_device.platform != "cpu"),
                 f"fused update donation on {ctx}: {donated.is_deleted()}")
    out = {"ctx": str(ctx), "tail": tail,
           "donated": bool(donated.is_deleted()),
           "first_loss": losses[0], "last_loss": losses[-1],
           "seconds": round(dt, 2)}
    print(f"[gluon] {out}", flush=True)
    return out


def attention_phase(shapes=ATTENTION_SHAPES, *, dtype="bfloat16",
                    expect_mosaic=True,
                    train_shapes=ATTENTION_TRAIN_SHAPES) -> dict:
    """`_attend` against `dot_product_attention_ref` at bf16 tolerance
    (the dropout-free call, `_attend` called directly), then the training
    stage below.  With `expect_mosaic` the lowered program must hold the
    Mosaic custom call, so the XLA reference cannot pass in its place."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    out = {}
    for bh, seq, dim, causal in shapes:
        rng = np.random.RandomState(seq)
        q, k, v = (jnp.asarray(rng.randn(bh, seq, dim), dtype)
                   for _ in range(3))
        # validity mask: each row keeps a prefix of at least seq/2 keys
        valid = rng.randint(seq // 2, seq + 1, size=(bh,))
        mask = jnp.asarray(np.arange(seq)[None, :] < valid[:, None], dtype)
        scale = 1.0 / np.sqrt(dim)
        kernel = jax.jit(lambda q, k, v, m, c=causal:
                         pa._attend(q, k, v, m, scale, c))
        if expect_mosaic:
            text = kernel.lower(q, k, v, mask).as_text()
            _require("tpu_custom_call" in text,
                     f"no Mosaic custom call in the lowered program for "
                     f"S={seq}")
        t0 = time.perf_counter()
        got = np.asarray(kernel(q, k, v, mask), np.float32)
        seconds = time.perf_counter() - t0
        ref = np.asarray(jax.jit(
            lambda q, k, v, m, c=causal: pa.dot_product_attention_ref(
                q, k, v, m, scale, c))(q, k, v, mask), np.float32)
        _require(np.isfinite(got).all(), f"non-finite output at S={seq}")
        err = float(np.abs(got - ref).max())
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)
        name = f"bh{bh}_s{seq}_d{dim}" + ("_causal" if causal else "")
        out[name] = {"max_abs_err": round(err, 5),
                     "first_call_s": round(seconds, 2)}
    out.update(attention_train_stage(train_shapes, dtype=dtype,
                                     expect_mosaic=expect_mosaic))
    print(f"[attention] {out}", flush=True)
    return out


def attention_train_stage(shapes, *, dtype="bfloat16", expect_mosaic=True,
                          dropout=0.1, n_dev=1) -> dict:
    """Value and `jax.grad` of `dot_product_attention` in training with
    dropout on the probabilities, through the op's own route, against
    `_attention_with_prob_dropout` (XLA, heads split off in HBM) under the
    IDENTICAL mask: both take it from the same hash of (key, b*h, q, k).
    Prefix-valid masks include lengths 2 and 3, so whole key blocks are
    masked for some rows.  With `expect_mosaic` the lowered gradient must
    hold the forward and the backward kernel and must have counted
    `fused_train`.  With `n_dev` > 1 the batch is `n_dev` times the
    shape's, split over a dp mesh, and the route is traced inside the
    mesh's scope as `SPMDTrainer` traces a step: GSPMD cannot partition a
    Mosaic call, so the route runs one call a chip, and the mask, indexed
    by GLOBAL batch row, must still be the one-program reference's."""
    import contextlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from mxnet_tpu import parallel
    from mxnet_tpu.ops import pallas_attention as pa

    out = {}
    mesh = parallel.make_mesh(dp=n_dev) if n_dev > 1 else None
    for batch, heads, seq, dim in shapes:
        batch *= n_dev
        rng = np.random.RandomState(seq)
        # packed (B, S, H*D), as the q/k/v projections hand it over
        q, k, v, ct = (jnp.asarray(rng.randn(batch, seq, heads * dim), dtype)
                       for _ in range(4))
        valid = rng.randint(2, seq + 1, size=(batch,))
        valid[:3] = (2, 3, seq)
        mask = jnp.asarray(np.arange(seq)[None, :] < valid[:, None], dtype)
        key = jax.random.PRNGKey(seq)
        scale = 1.0 / np.sqrt(dim)

        # everything an argument: a closed-over array is a constant of
        # the program (a 220 MB executable at these shapes)
        def weighed(o, ct):
            return (o.astype(jnp.float32) * ct.astype(jnp.float32)).sum(), o

        def route(q, k, v, ct, mask, key):
            with mesh or contextlib.nullcontext():
                return weighed(pa._dot_product_attention(
                    q, k, v, mask, key, num_heads=heads, dropout=dropout,
                    _train=True), ct)

        def reference(q, k, v, ct, mask, key):
            return weighed(pa._train_xla(
                q, k, v, mask, pa._seed(pa._key_words(key)), heads, scale,
                1.0 - dropout), ct)

        operands = (q, k, v, ct, mask, key)
        if mesh is not None:
            rows = NamedSharding(mesh.mesh, PartitionSpec("dp"))
            operands = (*(jax.device_put(x, rows) for x in operands[:5]),
                        jax.device_put(key, NamedSharding(
                            mesh.mesh, PartitionSpec())))
        grad = jax.jit(jax.grad(route, argnums=(0, 1, 2), has_aux=True))
        if expect_mosaic:
            before = pa.route_counts()["fused_train"]
            text = grad.lower(*operands).as_text()
            _require(pa.route_counts()["fused_train"] == before + 1,
                     f"the training call at S={seq} did not take the "
                     f"fused route: {pa.route_counts()}")
            for name in ("mx_attention_train_fwd", "mx_attention_train_bwd"):
                _require(name in text and "tpu_custom_call" in text,
                         f"no Mosaic call {name} in the lowered gradient "
                         f"at S={seq}")
        t0 = time.perf_counter()
        got = jax.block_until_ready(grad(*operands))
        seconds = time.perf_counter() - t0
        ref = jax.jit(jax.grad(reference, argnums=(0, 1, 2),
                               has_aux=True))(*operands)
        errs = {}
        for name, a, b in zip(("dq", "dk", "dv", "o"),
                              (*got[0], got[1]), (*ref[0], ref[1])):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            _require(np.isfinite(a).all(),
                     f"non-finite {name} from the training route at S={seq}")
            # bf16 operands on both sides: hold the error to the size of
            # the tensor, as rounding of a sum over S keys is
            errs[name] = float(np.abs(a - b).max() / np.abs(b).max())
            _require(errs[name] < 2e-2,
                     f"{name} of the training route at S={seq} is off the "
                     f"reference under the same mask by {errs[name]:.4f} of "
                     f"its largest value")
        out[f"train_b{batch}_h{heads}_s{seq}_d{dim}" + (
                f"_dp{n_dev}" if mesh is not None else "")] = {
            "max_rel_err": {n: round(e, 5) for n, e in errs.items()},
            "first_call_s": round(seconds, 2)}
    return out


def decoder_phase(*, seq=1024, heads=8, kv_heads=2, dim=128, window=256,
                  tokens=2048, experts=32, held=8, top_k=4, held_bias=0.2,
                  latent=256, width=384,
                  scan=(8192, 128, 64, 8, 128), dtype="bfloat16",
                  expect_mosaic=True) -> dict:
    """Value and gradients of the decoders' kernel routes against plain
    XLA on the same operands: causal grouped-query attention through the
    op (upstream's splash multi-query forward kernel over a causal mask
    and `mx_causal_attention_bwd`) against the S x S reference, grouped
    over one key block and one head a key/value head over two, the same
    under a sliding `window` at eight query heads a key/value head
    (upstream's forward kernel over the band and
    `mx_window_attention_bwd`) against the banded XLA form, the held
    experts' stage in both forms
    (relu^2 and silu-gated) (a loop over chunks of the plan's rows around
    the grouped-matmul kernel; `held_bias` on the router draws enough
    tokens to the held experts that it runs twice, a group across the
    boundary and an empty tail in the second chunk) against every held
    expert applied densely to every token, and one Mamba-2 scan (`scan`: S, H,
    P, G, N; the published widths at S = 8192) through `ops.pallas_ssd`'s
    kernels against the `chunked_xla` route.  The benchmark's reference
    check sees only their forward."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops import ssm
    from mxnet_tpu.parallel import moe

    rng = np.random.RandomState(0)

    def weighed(o, ct):
        return (o.astype(jnp.float32) * ct.astype(jnp.float32)).sum(), o

    def compare(name, route, plain, operands, out, looped=False, kernel=""):
        grad = jax.jit(jax.grad(route, argnums=(0, 1, 2), has_aux=True))
        if expect_mosaic:
            traced = grad.trace(*operands)
            text = traced.lower().as_text()
            _require("tpu_custom_call" in text and kernel in text,
                     f"no Mosaic call {kernel} in the lowered gradient of "
                     f"{name}")
            _require(not looped
                     or _holds(traced.jaxpr, "pallas_call", "while"),
                     f"no loop around the Mosaic calls in the gradient of "
                     f"{name}")
        got = jax.block_until_ready(grad(*operands))
        ref = jax.jit(jax.grad(plain, argnums=(0, 1, 2),
                               has_aux=True))(*operands)
        errs = {}
        for i, (a, b) in enumerate(zip((*got[0], got[1]),
                                       (*ref[0], ref[1]))):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            _require(np.isfinite(a).all(), f"non-finite result from {name}")
            errs[i] = float(np.abs(a - b).max() / np.abs(b).max())
            _require(errs[i] < 2e-2,
                     f"{name}: result {i} is off plain XLA by "
                     f"{errs[i]:.4f} of its largest value")
        out[name] = {"max_rel_err": round(max(errs.values()), 5)}

    out = {}

    def split(q, k, v, heads=heads, kv_heads=kv_heads):
        return (pa._split_to_heads(q, heads), pa._split_to_heads(k, kv_heads),
                pa._split_to_heads(v, kv_heads))

    def causal(seq, heads, kv_heads):
        """The causal op at these sizes against the S x S reference."""
        q, ct = (jnp.asarray(rng.randn(1, seq, heads * dim), dtype)
                 for _ in range(2))
        k, v = (jnp.asarray(rng.randn(1, seq, kv_heads * dim), dtype)
                for _ in range(2))
        before = pa.route_counts()["flash_causal"]

        def attention(q, k, v, ct):
            return weighed(pa._dot_product_attention(
                q, k, v, None, None, num_heads=heads, num_kv_heads=kv_heads,
                causal=True, _train=True), ct)

        def attention_plain(q, k, v, ct):
            o = pa._causal_xla(*split(q, k, v, heads, kv_heads), dim ** -0.5)
            return weighed(o.transpose(0, 2, 1, 3).reshape(q.shape), ct)

        compare(f"causal_gqa_h{heads}_kv{kv_heads}_s{seq}_d{dim}", attention,
                attention_plain, (q, k, v, ct), out,
                kernel="mx_causal_attention_bwd")
        _require(pa.route_counts()["flash_causal"] > before,
                 f"the causal call did not take the splash kernels' route: "
                 f"{pa.route_counts()}")
        return q, k, v, ct

    # one query head a key/value head over two key blocks: the backward
    # kernel reads the last query block's dQ in the step after the one
    # that wrote it (`pallas_attention._triangle_walk`'s `again`)
    causal(2 * seq, kv_heads, kv_heads)
    _, k, v, _ = causal(seq, heads, kv_heads)

    # the window at laguna's grouping, eight query heads a key/value head:
    # the band kernel's three gradients against the banded XLA form's
    grouped = 8 * kv_heads
    q, ct = (jnp.asarray(rng.randn(1, seq, grouped * dim), dtype)
             for _ in range(2))
    before = pa.route_counts()["splash_window"], pa.backward_counts()

    def windowed(q, k, v, ct):
        return weighed(pa._sliding_window_attention(
            q, k, v, num_heads=grouped, num_kv_heads=kv_heads,
            window=window), ct)

    def windowed_plain(q, k, v, ct):
        o = pa._window_xla(*split(q, k, v, grouped), dim ** -0.5, window)
        return weighed(o.transpose(0, 2, 1, 3).reshape(q.shape), ct)

    compare(f"window{window}_gqa_h{grouped}_kv{kv_heads}_s{seq}_d{dim}",
            windowed, windowed_plain, (q, k, v, ct), out,
            kernel="mx_window_attention_bwd")
    _require(pa.route_counts()["splash_window"] > before[0],
             f"the windowed call did not take the splash route: "
             f"{pa.route_counts()}")
    moved = {form: n - before[1][form]
             for form, n in pa.backward_counts().items()}
    _require(moved["band"] > 0 and moved["fused"] == moved["split"] == 0,
             f"the windowed call's backward is not the band kernel: {moved}")

    x = jnp.asarray(rng.randn(tokens, 64), jnp.float32)
    plan = moe.route(x, jnp.asarray(rng.randn(experts, 64), jnp.float32),
                     jnp.zeros((experts,), jnp.float32).at[:held].set(
                         held_bias), top_k=top_k,
                     scale=2.5, first_expert=0, n_local=held)
    _require(int(plan.dropped) == 0 and int(plan.group_sizes.sum()) > 0,
             "the router dropped assignments or placed none")
    rows, chunks = plan.token.shape[0], int(moe.plan_chunks(plan.group_sizes))
    _require(rows <= moe.ROW_CHUNK or (
        chunks > 1 and int(plan.group_sizes.sum()) < rows),
        f"{chunks} chunk(s) of {rows} rows: the experts' loop is not tried")
    u, ct = (jnp.asarray(rng.randn(tokens, latent), dtype) for _ in range(2))
    w1 = jnp.asarray(rng.randn(held, latent, width) * 0.1, dtype)
    w2 = jnp.asarray(rng.randn(held, width, latent) * 0.1, dtype)

    def grouped(form):
        def f(u, w1, w2, ct, token, weight, sizes):
            return weighed(moe.experts(
                u, moe.RoutePlan(token, weight, sizes, plan.dropped), w1, w2,
                form), ct)
        return f

    def dense(form):
        def f(u, w1, w2, ct, token, weight, sizes):
            # (tokens, held) combine weights back from the rows' layout
            expert = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(
                token.shape[0]), side="right")
            table = jnp.zeros((tokens, held + 1), jnp.float32).at[
                token, jnp.minimum(expert, held)].add(weight, mode="drop")
            total = jnp.zeros((tokens, latent), jnp.float32)
            for e in range(held):
                hidden = u @ w1[e]
                if form == "relu2":
                    hidden = jnp.square(jnp.maximum(hidden, 0))
                else:
                    hidden = (jax.nn.silu(hidden[:, :width])
                              * hidden[:, width:])
                total += table[:, e, None] * (hidden @ w2[e]).astype(
                    jnp.float32)
            return weighed(total.astype(u.dtype), ct)
        return f

    gated_w1 = jnp.asarray(rng.randn(held, latent, 2 * width) * 0.1, dtype)
    for form, first in (("relu2", w1), ("silu_gated", gated_w1)):
        name = "experts" if form == "relu2" else "gated_experts"
        compare(f"{name}_t{tokens}_held{held}_k{latent}_n{width}",
                grouped(form), dense(form),
                (u, first, w2, ct, plan.token, plan.weight,
                 plan.group_sizes), out, looped=True)
    # every grouped product ran on a tile that divides its own k and n
    _require(not expect_mosaic or (moe.route_counts()["exact_tiles"] and not
             moe.route_counts()["padded_tiles"]), f"{moe.route_counts()}")
    print(f"[tiles] {moe.tile_choices()}")

    s, h, p, g, n = scan
    x, ct = (jnp.asarray(rng.randn(1, s, h, p), dtype) for _ in range(2))
    bm, cm = (jnp.asarray(rng.randn(1, s, g, n) * 0.5, dtype)
              for _ in range(2))
    rest = (jnp.asarray(rng.randn(1, s, h), dtype),
            jnp.asarray(np.log(rng.uniform(1, 16, h)), jnp.float32),
            jnp.asarray(rng.randn(h), jnp.float32),
            jnp.asarray(rng.randn(h) - 3.0, jnp.float32))
    before = ssm.route_counts()["fused_kernel"]

    def scanned(route):
        def f(x, bm, cm, ct, dt, a_log, d, dt_bias):
            return weighed(route(x, dt, a_log, bm, cm, d, dt_bias,
                                 chunk=128), ct)
        return f

    compare(f"ssd_scan_s{s}_h{h}_p{p}_g{g}_n{n}", scanned(ssm._ssd_scan),
            scanned(ssm._scan_xla), (x, bm, cm, ct, *rest), out)
    _require(ssm.route_counts()["fused_kernel"] > before,
             f"the scan did not take the kernel route: {ssm.route_counts()}")
    return out


def result_line(devices) -> str:
    """The last line of stdout: exactly `ok` and `device`, and `device`
    exactly `platform`, `kind`, `count`.  Only a run in which every phase
    passed gets here - a failed phase has already raised."""
    return json.dumps({
        "ok": True,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)}})


def main() -> int:
    t_start = time.perf_counter()
    from mxnet_tpu.compile_cache import jax_cache

    cache = jax_cache.configure()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices() is {devices} (platform "
              f"{devices[0].platform!r}); this script does not run on "
              "another platform", file=sys.stderr)
        return 2
    import mxnet_tpu as mx

    print(f"[cache] jax compilation cache at {cache.directory}", flush=True)
    phases = {}

    def run(name, fn, *a, **kw):
        t0 = time.perf_counter()
        result = fn(*a, **kw)
        phases[name] = {"ok": True,
                        "seconds": round(time.perf_counter() - t0, 1),
                        **{k: result[k] for k in
                           ("n_dev", "first_loss", "jax_cache",
                            "ms_per_step", "tail", "peak_bytes_in_use")
                           if k in result}}
        return result

    info = run("device", device_phase)
    one = run("resnet50", resnet_phase, cache)
    run("gluon", gluon_phase, mx.tpu(0))
    run("attention", attention_phase)
    run("decoder", decoder_phase)
    n = len(devices)
    if n > 1:
        run("attention_dp", attention_train_stage, ATTENTION_TRAIN_SHAPES,
            n_dev=n)
        many = run("multichip", resnet_phase, cache, n_dev=n,
                   batch=256 * n, tile=n)
        # same seed, the base batch repeated on every chip: the first
        # step's loss is the one-chip loss up to bf16 reduction order
        np.testing.assert_allclose(many["first_loss"], one["first_loss"],
                                   rtol=2e-2)
    summary = {"versions": {k: info[k] for k in ("jax", "jaxlib", "libtpu")},
               "phases": phases,
               "seconds": round(time.perf_counter() - t_start, 1)}
    print(f"[summary] {json.dumps(summary)}", flush=True)
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
