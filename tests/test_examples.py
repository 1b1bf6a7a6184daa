"""Example scripts end-to-end (CPU smoke of BASELINE configs 3-5 real-data
paths; ref: example/ scripts).  Each runs the actual script in a
subprocess the way a user would."""
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable] + args, cwd=_REPO, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout + r.stderr  # logging writes to stderr


def test_bert_pretrain_corpus(tmp_path):
    rng = np.random.RandomState(0)
    words = [f"w{i}" for i in range(150)]
    corpus = tmp_path / "corpus.txt"
    with open(corpus, "w") as f:
        for _ in range(40):
            sents = [" ".join(rng.choice(words, rng.randint(4, 9)))
                     for _ in range(rng.randint(2, 4))]
            f.write(". ".join(sents) + "\n")
    out = _run(["examples/bert_pretrain.py", "--cpu", "--small",
                "--corpus", str(corpus), "--steps", "2"])
    assert "step 1: loss=" in out


@pytest.mark.slow  # ~35s: rec-file build + SSD train loop; nightly
def test_ssd_train_rec(tmp_path):
    from mxnet_tpu import recordio as rio

    try:
        from mxnet_tpu.image import imencode

        _ = imencode(np.zeros((4, 4, 3), np.uint8))
    except Exception:
        pytest.skip("no image encoder available")
    rng = np.random.RandomState(0)
    rec_path = str(tmp_path / "det.rec")
    rec = rio.MXRecordIO(rec_path, "w")
    for i in range(8):
        img = (rng.rand(140, 140, 3) * 255).astype(np.uint8)
        objs = [float(i % 3), 0.1, 0.15, 0.6, 0.7]
        h = rio.IRHeader(0, np.asarray([2, 5] + objs, np.float32), i, 0)
        rec.write(rio.pack_img(h, img))
    rec.close()
    out = _run(["examples/ssd_train.py", "--cpu", "--small",
                "--batch-size", "4", "--rec", rec_path, "--epochs", "1"],
               timeout=560)
    assert "decoded" in out and "loss=" in out


@pytest.mark.slow  # 9s example train loop; mnist/long-context keep
# tier-1 example coverage, the heavy-integration stage runs this nightly
def test_transformer_nmt_parallel_corpus(tmp_path):
    rng = np.random.RandomState(1)
    src, tgt = tmp_path / "train.src", tmp_path / "train.tgt"
    with open(src, "w") as fs, open(tgt, "w") as ft:
        for _ in range(80):
            n = rng.randint(3, 12)
            toks = [f"s{rng.randint(60)}" for _ in range(n)]
            fs.write(" ".join(toks) + "\n")
            ft.write(" ".join(t.replace("s", "t")
                              for t in reversed(toks)) + "\n")
    out = _run(["examples/transformer_nmt.py", "--cpu", "--small",
                "--src", str(src), "--tgt", str(tgt), "--epochs", "1"])
    assert "avg-loss=" in out


@pytest.mark.slow  # ~16s: 2-epoch bucketed RNN example; nightly
def test_rnn_bucketing_symbolic():
    out = _run(["examples/rnn_bucketing.py", "--cpu", "--small",
                "--epochs", "2"], timeout=560)
    assert "Train-perplexity" in out and "final perplexity=" in out
    # the synthetic alphabet task is very learnable
    ppl = float(out.rsplit("final perplexity=", 1)[1].splitlines()[0])
    assert ppl < 3.0, ppl


@pytest.mark.slow  # ~15s: entropy calibration sweep; nightly
def test_quantize_model_example():
    out = _run(["examples/quantize_model.py", "--cpu", "--small",
                "--calib-mode", "entropy"], timeout=560)
    assert "int8 (entropy): accuracy=" in out
    assert "accuracy drop:" in out


@pytest.mark.parametrize("method", ["ring", "ulysses"])
def test_long_context_lm_example(method):
    out = _run(["examples/long_context_lm.py", "--cpu", "--method", method,
                "--dp", "2", "--sp", "4", "--steps", "5",
                "--seq-len", "64", "--units", "32", "--heads", "4",
                "--layers", "1", "--vocab", "128"])
    assert "loss" in out and "sp=4" in out


@pytest.mark.slow  # ~23s: legacy-cell RNN example; nightly
def test_rnn_bucketing_legacy_cells():
    out = _run(["examples/rnn_bucketing.py", "--cpu", "--small",
                "--cells"])
    assert "perplexity" in out


def test_mnist_gluon_example():
    """The SURVEY minimum-slice script (examples/gluon/mnist.py): val
    accuracy parsed from the output must clear the script's own bar."""
    import re

    out = _run(["examples/gluon/mnist.py", "--cpu", "--epochs", "1",
                "--batch-size", "50"], timeout=420)
    m = re.search(r"\[val\] accuracy=([0-9.]+)", out)
    assert m, out[-500:]
    assert float(m.group(1)) > 0.9


@pytest.mark.slow  # ~34s: synthetic imagenet train loop; nightly
def test_imagenet_train_synthetic():
    import re

    out = _run(["examples/imagenet_train.py", "--synthetic-data",
                "--image-size", "32", "--per-class", "8", "--classes", "4",
                "--batch-size", "8", "--epochs", "1"], timeout=420)
    assert "data pipeline:" in out          # the native path engaged
    m = re.search(r"([0-9.]+) img/s", out)
    assert m and float(m.group(1)) > 0
