"""Operations a training step REQUIRES, from its shapes — the numerator of
``train.mfu`` — and the chip's peak, its denominator.  Kept here, not read from the program: ``bench_train.
_train_flops`` counts one layer's attention and the masked half of the
causal matrix (PERF.md, PR 24), and a later PR must not be able to move
the yardstick.

Per token, ``6 x`` the parameters that take part in a matrix
multiplication (forward 2, backward 4): the four projections and two FFN
matrices of each layer and the unembedding.  The embedding table is a
gather and counts nothing.  Attention adds, per layer, the score and the
value products (2 x 2 x B x H x T^2 x D forward, twice that backward),
halved because a causal step needs only the lower triangle.  Recomputed
operations (the chunked loss's second logits pass, flash attention's
score recompute in its backward kernels) are not required and not
counted.
"""

from __future__ import annotations

import json
import os


def matmul_params(model: dict) -> int:
    E, H, D, F, V, L = (model["embed"], model["n_heads"], model["head_dim"],
                        model["ffn"], model["vocab"], model["n_layers"])
    per_layer = E * 3 * H * D + H * D * E + E * F + F * E
    return L * per_layer + E * V


def train_step_flops(model: dict, batch: int, seq_len: int) -> float:
    dense = 6.0 * matmul_params(model) * batch * seq_len
    attention = (3.0 * 2 * 2 * batch * model["n_heads"] * seq_len ** 2
                 * model["head_dim"] * model["n_layers"] / 2)
    return dense + attention


def peak_flops(device_kind: str):
    """bf16 FLOP/s of one chip of *device_kind* by ``peaks.json``, or
    ``None`` for a kind the table does not hold."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        entry = json.load(f)["by_device_kind"].get(device_kind)
    return entry and entry["flops_bf16"]
