"""Operations a training step of a LOOPED language model requires, from
its shapes — the numerator of ``looplm.mfu``.  Kept with the benchmark,
like ``flops.py``, so that a PR that changes the program cannot move the
yardstick; the chip's peak is ``flops.peak_flops``.

The stack of ``L`` layers is applied ``R`` times to every token, and the
head after every pass, so every matrix that takes part in a product is
used ``R`` times a token.  Per token, ``6 x`` (forward 2, backward 4)
``R x`` those parameters: the four ``E x (H D)`` attention projections
and the three ``E x F`` matrices of the gated FFN in each layer, and the
``E x V`` head.  The embedding table is a gather and counts nothing.
Attention adds, per layer and pass, the score and the value products
(``2 x 2 x B x H x T^2 x D`` forward, twice that backward), halved
because a causal step needs only the lower triangle.

Not required, and not counted: the forward pass that per-layer
recomputation runs again, the chunked loss's second logits pass, flash
attention's score recompute in its backward kernel, the exit gate's ``E``
products a token and pass, the norms and the rotary embedding.
"""

from __future__ import annotations


def matmul_params_per_pass(model: dict) -> int:
    """Parameters that take part in a matrix product in ONE pass over
    the stack and its head."""
    E, H, D, F, V, L = (model["embed"], model["n_heads"], model["head_dim"],
                        model["ffn"], model["vocab"], model["n_layers"])
    return L * (4 * E * H * D + 3 * E * F) + E * V


def train_step_flops(model: dict, batch: int, seq_len: int) -> float:
    R = model["loop_steps"]
    dense = 6.0 * R * matmul_params_per_pass(model) * batch * seq_len
    attention = (3.0 * 2 * 2 * batch * model["n_heads"] * seq_len ** 2
                 * model["head_dim"] * model["n_layers"] * R / 2)
    return dense + attention
