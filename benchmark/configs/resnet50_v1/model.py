"""resnet50_v1 as the benchmark runs it: the model-zoo block through the
program's normal training path, the batch, and the FLOPs the model needs.
"""
from __future__ import annotations

import numpy as np

SAMPLES_UNIT = "images"


def build(seed, config, traffic, chips):
    """examples/imagenet_train.py's construction: zoo block initialised on
    cpu() from the seed, cast, SPMDTrainer over make_mesh(dp=chips)."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    np.random.seed(seed)
    mx.random.seed(seed)
    if (config["layers"], config["block"]) != ([3, 4, 6, 3],
                                               "bottleneck_v1"):
        raise ValueError("this file builds the zoo entry resnet50_v1: "
                         "bottleneck_v1 blocks, layers [3, 4, 6, 3]")
    net = vision.resnet50_v1(classes=config["classes"],
                             layout=config["layout"])
    net.initialize(mx.initializer.Xavier(magnitude=2.0), ctx=mx.cpu())
    with mx.autograd.pause():       # resolve deferred shapes, cheaply
        net(mx.nd.zeros((1, 32, 32, 3), ctx=mx.cpu()))
    net.cast(config["dtype"])
    opt = dict(config["optimizer"])
    return parallel.SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), opt.pop("name"), opt,
        mesh=parallel.make_mesh(dp=chips))


def batch(seed, config, traffic, put):
    """The resident batch: (images, labels), drawn on the device from the
    seed (`put(draw, key)` places what `draw(key)` makes)."""
    import jax

    n, hw = traffic["batch"], traffic["image_size"]
    k_img, k_lab = jax.random.split(jax.random.PRNGKey(seed))
    images = put(lambda k: jax.random.uniform(
        k, (n, hw, hw, 3), config["dtype"]), k_img)
    labels = put(lambda k: jax.random.randint(
        k, (n,), 0, config["classes"], "int32"), k_lab)
    return images, labels


def sample(seed, config, traffic):
    """The seeded inputs the reference check runs on."""
    rng = np.random.RandomState(seed)
    n, hw = config["reference_check"]["sample"], traffic["image_size"]
    return (rng.rand(n, hw, hw, 3).astype(np.float32),)


def system_logits(trainer, sample, config):
    """Inference-mode logits from the placed parameters."""
    import jax.numpy as jnp

    out = trainer.forward(jnp.asarray(sample[0], config["dtype"]))
    return {"logits": np.asarray(out.data, np.float32)}


def reference_logits(reference, params, sample, config):
    import jax.numpy as jnp

    # the reference sees the images the system saw: rounded to bfloat16
    import jax

    images = jnp.asarray(sample[0], config["dtype"]).astype(jnp.float32)
    return {"logits": np.asarray(jax.jit(
        lambda p, x: reference.logits(p, x, config))(params, images),
        np.float32)}


def reference_first_loss(reference, params, batch, config):
    """The training-mode loss of step 1 on the resident batch, from the
    reference: BatchNorm's arithmetic cannot change unseen."""
    import jax

    images, labels = batch
    return float(jax.jit(
        lambda p, x, y: reference.loss(p, x, y, config))(
            params, images, labels))


def flops_per_sample(config, traffic):
    """Trained FLOPs per image from the layer shapes: 2 per multiply-add,
    backward = 2 x forward, no recomputation, no optimizer, and none for
    BatchNorm, ReLU, pooling or the loss (under 1% of the total)."""
    return 3 * 2 * forward_macs(config, traffic["image_size"])


def forward_macs(config, image_size):
    """Multiply-adds of one forward pass: every convolution and the
    classifier, v1 placement of the stride (first 1x1)."""
    def conv(hw_out, k, cin, cout):
        return hw_out * hw_out * k * k * cin * cout

    hw = image_size // 2                              # 7x7 stride 2
    macs = conv(hw, 7, 3, config["stem_channels"])
    hw //= 2                                          # 3x3 max-pool stride 2
    cin = config["stem_channels"]
    for s, (blocks, cout) in enumerate(zip(config["layers"],
                                           config["channels"])):
        mid = cout // 4
        for b in range(blocks):
            if b == 0 and s > 0:
                hw //= 2          # the stride sits on the first 1x1
            macs += conv(hw, 1, cin, mid) + conv(hw, 3, mid, mid) \
                + conv(hw, 1, mid, cout)
            if b == 0:
                macs += conv(hw, 1, cin, cout)        # projection shortcut
            cin = cout
    return macs + cin * config["classes"]
