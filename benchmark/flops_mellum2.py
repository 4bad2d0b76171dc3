"""Operations a training step of a model with sliding-window layers beside
full ones and routed experts REQUIRES, from its shapes and from the
routing that took place — the numerator of ``mellum.mfu`` — and what one
call of a windowed flash kernel requires, for its roofline.  Kept with
the benchmark, like ``flops.py``, so that a PR that changes the program
cannot move the yardstick; the chip's peak is ``flops.peak_flops``.

An attention product (scores ``QK^T``, values ``PV``, and in the backward
pass ``dP = dO V^T``, ``dQ = dS K``, ``dV = P^T dO``, ``dK = dS^T Q``:
two forward, four backward) costs 2 operations for each allowed (q, k)
pair, head and head dimension.  A full layer allows ``T^2 / 2`` pairs a
sequence (the causal triangle, as ``flops.py`` counts it); a windowed one
``0 <= q - k < W``: ``W T - W (W - 1) / 2`` of them.  Per token and
forward pass, in multiply-adds, beside that: ``E x H D`` for q, ``E x 2
Hkv D`` for k and v, ``H D x E`` out, the router's ``E x X`` over all
``X`` experts, in every layer; the untied head ``E x V``.  The experts'
three ``E x Fe`` matrices count once for every (token, expert) PAIR that
landed on an expert held here: a number of the run, read from the step's
statistics.  Times 2 for operations, times 3 for forward and backward.

Not required, and not counted: the forward pass remat runs again, the
chunked loss's second logits pass, the flash kernels' score recompute,
the masked part of a tile that crosses the diagonal or the window's edge
(at ``W`` = a tile's 1024 every tile of a windowed layer is half masked:
the kernels execute twice what is required there), K and V repeated over
a group's query heads, the zero rows that pad an expert's rows to a
tile, the norms, the rotary embedding, the gates.
"""

from __future__ import annotations

#: a windowed kernel's required products, the [B, H, T, D] tensors of
#: the model's 2-byte type it reads plus writes once, such tensors in
#: float32, and its [B, H, T] float32 row statistics.  ``flash_dkv_win``
#: is the whole backward tile loop (all four products; it writes the
#: float32 dQ accumulator), ``flash_dq_win`` the scale-and-cast of that
#: accumulator
_WINDOW_KERNELS = {
    "flash_fwd_win": (2, 4, 0, 1),     # Q K V -> O, lse
    "flash_dkv_win": (4, 6, 1, 2),     # Q K V dO lse delta -> dK dV dQ^
    "flash_dq_win": (0, 1, 1, 0),      # dQ^ -> dQ
}


def attended_pairs(seq_len: int, window=None) -> float:
    """Allowed (q, k) pairs of one sequence and head."""
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2.0
    return float(window * seq_len - window * (window - 1) // 2)


def layer_windows(model: dict) -> list:
    """Each layer's window, ``None`` for a full layer."""
    ops = model.get("layer_ops") or ["attn"] * model["n_layers"]
    return [model["attn_window"] if op == "window" else None for op in ops]


def attention_product_flops(model: dict, batch: int, seq_len: int,
                            window=None) -> float:
    """ONE attention product of one layer: ``2 B H D`` a pair."""
    return (2.0 * batch * model["n_heads"] * model["head_dim"]
            * attended_pairs(seq_len, window))


def dense_macs_per_token(model: dict) -> float:
    """Multiply-adds a token and forward pass, attention's products and
    the experts aside."""
    E, H, D, V = (model["embed"], model["n_heads"], model["head_dim"],
                  model["vocab"])
    Hkv = model.get("n_kv_heads") or H
    layer = 2 * E * H * D + 2 * E * Hkv * D + E * model["moe_experts"]
    return float(model["n_layers"] * layer + E * V)


def expert_macs_per_pair(model: dict) -> float:
    return 3.0 * model["embed"] * model["moe_ffn"]


def train_step_flops(model: dict, batch: int, seq_len: int,
                     pairs_held: float) -> float:
    """*pairs_held*: the (token, expert) pairs that landed on held
    experts in the step, summed over the layers."""
    attention = sum(6.0 * attention_product_flops(model, batch, seq_len, w)
                    for w in layer_windows(model))
    return attention + 6.0 * (
        dense_macs_per_token(model) * batch * seq_len
        + expert_macs_per_pair(model) * pairs_held)


def expected_pairs_held(model: dict, batch: int, seq_len: int) -> float:
    """Pairs held a step under uniform routing: what a prediction uses."""
    held = model.get("moe_held") or model["moe_experts"]
    return (model["n_layers"] * batch * seq_len * model["moe_top_k"] * held
            / model["moe_experts"])


def window_call_work(kernel: str, model: dict, batch: int, seq_len: int):
    """``{"flops", "bytes"}`` one call of the windowed *kernel* (one
    layer's) requires, or ``None`` for a kernel this file has no rule
    for."""
    if kernel not in _WINDOW_KERNELS:
        return None
    products, tensors, wide, stats = _WINDOW_KERNELS[kernel]
    rows = batch * model["n_heads"] * seq_len
    return {"flops": products * attention_product_flops(
                model, batch, seq_len, model["attn_window"]),
            "bytes": float(rows * model["head_dim"] * (2 * tensors + 4 * wide)
                           + rows * 4 * stats)}
