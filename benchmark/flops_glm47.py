"""Operations a training step of a model with latent attention (MLA), a
shared expert beside routed ones and a multi-token-prediction block
REQUIRES, from its shapes and from the routing that took place — the
numerator of ``glm.mfu``.  Kept with the benchmark, like ``flops.py``, so
that a PR that changes the program cannot move the yardstick; the chip's
peak is ``flops.peak_flops``.

Per token and forward pass, in multiply-adds.  An attention layer (every
layer, and the prediction block's): latent attention's five products
``E x Rq``, ``Rq x H (dn + dr)``, ``E x (Rkv + dr)``, ``Rkv x H (dn +
dv)``, ``H dv x E``, and the score and value products at their own
widths, ``H (dn + dr)`` and ``H dv`` a (q, k) pair, ``T / 2`` pairs a
token (the causal triangle).  The leading dense layer's gated FFN ``3 x E
x F``.  An expert layer (the prediction block's among them): the router
``E x X`` over all ``X`` experts and the shared expert ``3 x E x Fs``,
every token; the routed experts' three ``E x Fe`` matrices once for every
(token, expert) PAIR that landed on an expert held here: a number of the
run, read from the step's statistics.  The prediction block's joining
``2E x E``.  The head ``E x V`` twice: the main loss and the block's.
Times 2 for operations, times 3 for forward and backward.

The flash kernels' own work at this head width is
``kernel_work.kernel_call_work`` with the configuration's ``head_dim``
(256): the rule reads it.

Not required, and not counted: the forward pass remat runs again, the
chunked loss's second logits pass, the flash kernels' score recompute,
the rotary key broadcast to the heads, the zero rows that pad an
expert's rows to a tile, the norms, the rotary embedding, the gates, the
bias's move.  The block's last position has no token after next and its
row is counted all the same: 1 position in ``T``.
"""

from __future__ import annotations


def attention_layers(model: dict) -> int:
    return model["n_layers"] + model.get("mtp_blocks", 0)


def expert_layers(model: dict) -> int:
    """Layers with a router, a prediction block's (of the last layer's
    kind) among them."""
    ffns = model.get("layer_ffns") or ["dense"] * model["n_layers"]
    return (sum(f == "moe" for f in ffns)
            + model.get("mtp_blocks", 0) * (ffns[-1] == "moe"))


def latent_macs_per_token(model: dict) -> float:
    """Latent attention's five products, one layer."""
    E, H = model["embed"], model["n_heads"]
    Rq, Rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_dim"], model["qk_rope_dim"],
                  model["v_head_dim"])
    return float(E * Rq + Rq * H * (dn + dr) + E * (Rkv + dr)
                 + Rkv * H * (dn + dv) + H * dv * E)


def attention_product_macs_per_token(model: dict, seq_len: int) -> float:
    """The score and the value product of one layer: ``T / 2`` pairs a
    token, ``H (dn + dr)`` and ``H dv`` a pair."""
    H = model["n_heads"]
    return (H * (model["qk_nope_dim"] + model["qk_rope_dim"]
                 + model["v_head_dim"]) * seq_len / 2.0)


def dense_macs_per_token(model: dict, seq_len: int) -> float:
    """Multiply-adds a token and forward pass, the routed experts
    aside."""
    E, F, V = model["embed"], model["ffn"], model["vocab"]
    ffns = model.get("layer_ffns") or ["dense"] * model["n_layers"]
    mtp = model.get("mtp_blocks", 0)
    macs = attention_layers(model) * (
        latent_macs_per_token(model)
        + attention_product_macs_per_token(model, seq_len))
    macs += sum(f == "dense" for f in ffns) * 3 * E * F
    macs += expert_layers(model) * (E * model["moe_experts"]
                                    + 3 * E * model.get("shared_ffn", 0))
    macs += mtp * 2 * E * E + (1 + mtp) * E * V
    return float(macs)


def expert_macs_per_pair(model: dict) -> float:
    return 3.0 * model["embed"] * model["moe_ffn"]


def train_step_flops(model: dict, batch: int, seq_len: int,
                     pairs_held: float) -> float:
    """*pairs_held*: the (token, expert) pairs that landed on held
    experts in the step, summed over the expert layers (the prediction
    block's among them)."""
    return 6.0 * (dense_macs_per_token(model, seq_len) * batch * seq_len
                  + expert_macs_per_pair(model) * pairs_held)


def expected_pairs_held(model: dict, batch: int, seq_len: int) -> float:
    """Pairs held a step under uniform routing: what a prediction uses."""
    held = model.get("moe_held") or model["moe_experts"]
    return (expert_layers(model) * batch * seq_len * model["moe_top_k"]
            * held / model["moe_experts"])
