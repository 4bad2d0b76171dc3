"""Operations a training step of a model with layers of several kinds and
routed experts REQUIRES, from its shapes and from the routing that took
place — the numerator of ``moe.mfu`` — and what one grouped product of the
experts requires, for its roofline.  Kept with the benchmark, like
``flops.py``, so that a PR that changes the program cannot move the
yardstick; the chip's peak is ``flops.peak_flops``.

Per token and forward pass, in multiply-adds: a convolution layer's two
projections ``E x 3E`` and ``E x E`` (the three taps a channel are
elementwise and count nothing); an attention layer's ``E x H D`` for q,
``E x 2 Hkv D`` for k and v, ``H D x E`` out, and the score and value
products ``2 x H x T x D`` halved for the causal triangle; a dense gated
FFN ``3 x E x F``; a routed layer's router ``E x X`` over all ``X``
experts; the tied head ``E x V``.  The experts' three ``E x Fe``
matrices count once for every (token, expert) PAIR that landed on an
expert held here: a number of the run, read from the step's statistics.
Times 2 for operations, times 3 for forward and backward.

Not required, and not counted: the forward pass remat runs again, the
chunked loss's second logits pass, the flash kernels' score recompute,
K and V repeated over a group's query heads, the zero rows that pad an
expert's rows to a tile, the norms, the rotary embedding, the gates.
"""

from __future__ import annotations


def kinds(model: dict) -> list:
    """``(operator, ffn)`` of each layer."""
    L = model["n_layers"]
    return list(zip(model.get("layer_ops") or ["attn"] * L,
                    model.get("layer_ffns") or ["dense"] * L))


def dense_macs_per_token(model: dict, seq_len: int) -> float:
    """Multiply-adds a token and forward pass, the experts aside."""
    E, H, D, F, V = (model["embed"], model["n_heads"], model["head_dim"],
                     model["ffn"], model["vocab"])
    Hkv = model.get("n_kv_heads") or H
    macs = float(E * V)
    for op, ffn in kinds(model):
        if op == "conv":
            macs += 4 * E * E
        else:
            macs += 2 * E * H * D + 2 * E * Hkv * D + 2 * H * D * seq_len / 2
        macs += E * model["moe_experts"] if ffn == "moe" else 3 * E * F
    return macs


def expert_macs_per_pair(model: dict) -> float:
    return 3.0 * model["embed"] * model["moe_ffn"]


def train_step_flops(model: dict, batch: int, seq_len: int,
                     pairs_held: float) -> float:
    """*pairs_held*: the (token, expert) pairs that landed on held
    experts in the step, summed over the expert layers."""
    return 6.0 * (dense_macs_per_token(model, seq_len) * batch * seq_len
                  + expert_macs_per_pair(model) * pairs_held)


def expected_pairs_held(model: dict, batch: int, seq_len: int) -> float:
    """Pairs held a step under uniform routing: what a prediction uses."""
    n_moe = sum(ffn == "moe" for _, ffn in kinds(model))
    held = model.get("moe_held") or model["moe_experts"]
    return (n_moe * batch * seq_len * model["moe_top_k"] * held
            / model["moe_experts"])


def grouped_product_work(model: dict, pairs: float, n_held: int,
                         out_bytes: int = 2) -> dict:
    """``{"flops", "bytes"}`` ONE grouped product over *pairs* rows
    requires (``moe_gmm`` forward or for the rows' gradient, ``moe_tgmm``
    for the weights' with ``out_bytes=4``): ``2 x pairs x E x Fe``
    operations; the rows read and written once in the 2-byte type, the
    held experts' matrices once."""
    E, Fe = model["embed"], model["moe_ffn"]
    return {"flops": 2.0 * pairs * E * Fe,
            "bytes": float(pairs * (E + Fe) * 2
                           + n_held * E * Fe * out_bytes)}
