"""Operations and bytes counted from shapes: a layer's FLOPs, a federated
round's model FLOPs, and the fused KD kernel's byte model.

Model FLOPs count the multiply-adds of the convolutions and dense layers
(2 per MAC).  Training is the forward pass, the weight gradients (as many
FLOPs as the forward) and the input gradients of every layer but the first
(autodiff takes none for the data).  Biases, activations and the optimizer
are left out: they are a few per parameter against hundreds per MAC.
"""
from __future__ import annotations

import math


def conv_flops(out_positions: int, window: int, cin: int, cout: int) -> int:
    return 2 * out_positions * window * cin * cout


def dense_flops(fan_in: int, fan_out: int) -> int:
    return 2 * fan_in * fan_out


def layer_flops(config: dict, student: bool) -> list[int]:
    """Forward FLOPs per example of each weight layer, input to output, by
    the configuration's dataset module (``models/<dataset>.py``)."""
    from bench import models
    m = config["model"]
    return models.of(config).layer_flops(
        m["student_filters" if student else "teacher_filters"],
        config["dataset"]["num_classes"])


def forward(config, student) -> int:
    return sum(layer_flops(config, student))


def train(config, student) -> int:
    """Forward + backward FLOPs per example."""
    layers = layer_flops(config, student)
    return 2 * sum(layers) + sum(layers[1:])


def round_flops(config: dict, client_rows: list[int],
                teacher_rows: list[int], eval_rows: int) -> int:
    """Model FLOPs of one FedSiKD round: every client's real rows through
    the student's training step and the teacher's forward (the KD target);
    each cluster teacher's refresh on its leader's real rows, once per
    cluster (the per-slot replicas repeat it); the test set through the
    student."""
    s_train, t_fwd = train(config, True), forward(config, False)
    return (sum(client_rows) * (s_train + t_fwd)
            + sum(teacher_rows) * train(config, False)
            + eval_rows * forward(config, True))


# ------------------------------------------------------- fused KD kernel
BLOCK_T = 128                      # the kernel's token block


def kd_kernel_bytes(tokens: int, vocab: int) -> dict:
    """Bytes one lane's forward and backward KD kernel call reads and
    writes, with the tokens padded to the 128-row block the kernel runs on:
    forward reads student and teacher logits and the labels, writes the
    per-row loss and its three log-normalisers; backward reads logits,
    labels, normalisers and the per-row cotangent, writes the logit
    gradient.  Operands are counted at their logical size: XLA may keep
    these small arrays in on-chip memory, so the tiles' lane padding is not
    traffic the kernel must make."""
    t = math.ceil(tokens / BLOCK_T) * BLOCK_T
    logits, col, stats = 4 * t * vocab, 4 * t, 4 * t * 3
    return {"fwd": 2 * logits + col + col + stats,
            "bwd": 2 * logits + col + stats + col + logits}


def kd_kernel_flops(tokens: int, vocab: int) -> dict:
    """Arithmetic per lane call, counting each add, multiply, divide,
    compare and transcendental once over the padded block."""
    e = math.ceil(tokens / BLOCK_T) * BLOCK_T * vocab
    return {"fwd": 30 * e, "bwd": 16 * e}
