"""The benchmark's operation and byte counts against hand counts."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_conv_layer_by_hand():
    # MNIST teacher conv 2: 14x14 -> 7x7 out, 3x3 window, 32 -> 64 channels
    assert flops.conv_flops(7 * 7, 9, 32, 64) == 2 * 49 * 9 * 32 * 64


def test_dense_layer_by_hand():
    # MNIST head: 2x2x64 features -> 10 classes
    assert flops.dense_flops(256, 10) == 2 * 256 * 10


def test_mnist_layers():
    t = flops.layer_flops(_config("mnist-cnn"), student=False)
    # 28 -> 14 -> 7 -> 4 -> 2 with stride 2 'same'
    assert t == [2 * 196 * 9 * 1 * 32, 2 * 49 * 9 * 32 * 64,
                 2 * 16 * 9 * 64 * 64, 2 * 4 * 9 * 64 * 64, 2 * 256 * 10]
    s = flops.layer_flops(_config("mnist-cnn"), student=True)
    assert s[1] == 2 * 49 * 9 * 32 * 16


def test_training_counts_no_input_gradient_for_the_first_layer():
    c = _config("mnist-cnn")
    layers = flops.layer_flops(c, student=True)
    assert flops.train(c, True) == 3 * sum(layers) - layers[0]


def test_round_flops_by_hand():
    c = _config("mnist-cnn")
    got = flops.round_flops(c, client_rows=[100, 50], teacher_rows=[40],
                            eval_rows=10)
    want = (150 * (flops.train(c, True) + flops.forward(c, False))
            + 40 * flops.train(c, False) + 10 * flops.forward(c, True))
    assert got == want


@pytest.mark.parametrize("tokens,vocab", [(64, 10), (64, 6), (128, 10)])
def test_kd_kernel_byte_model(tokens, vocab):
    b = flops.kd_kernel_bytes(tokens, vocab)
    t = 128                       # rows padded to the kernel's block
    assert b["fwd"] == 4 * (2 * t * vocab + t + t + 3 * t)
    assert b["bwd"] == 4 * (3 * t * vocab + t + 3 * t + t)
    f = flops.kd_kernel_flops(tokens, vocab)
    assert f["fwd"] == 30 * 128 * vocab and f["bwd"] == 16 * 128 * vocab
