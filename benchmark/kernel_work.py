"""Work one call of a kernel REQUIRES, from the configuration's shapes —
the numerator of ``<kernel>_roofline`` — whatever implements the kernel.

Kept with the benchmark like ``flops.py``, for the same reason: a PR
that rewrites a kernel must not be able to move its yardstick.

The flash kernels, causal, one call per layer.  The forward pass needs
two products (scores ``QK^T``, values ``PV``), the backward four:
``flash_dq`` is charged ``dP = dO V^T`` and ``dQ = dS K``, ``flash_dkv``
is charged ``dV = P^T dO`` and ``dK = dS^T Q``.  The scores a backward
kernel recomputes are not required work.  Each product is ``2 B H T^2 D``
operations, halved because a causal step needs only the lower triangle,
so every kernel has ``2 B H T^2 D`` a call and the three sum to
``flops.train_step_flops``'s attention term.  Bytes are the tensors a
call must read and write once, operands in the model's 2-byte type and
the two row statistics in float32.
"""

from __future__ import annotations

import json
import os

#: tensors of shape [B, H, T, D] each flash kernel reads plus writes, and
#: row statistics of shape [B, H, T] (log-sum-exp, delta)
_FLASH_TENSORS = {
    "flash_fwd": (4, 1),     # Q K V -> O, lse
    "flash_dq": (5, 2),      # Q K V dO lse delta -> dQ
    "flash_dkv": (6, 2),     # Q K V dO lse delta -> dK dV
}


def kernel_call_work(kernel: str, model: dict, batch: int,
                     seq_len: int):
    """``{"flops", "bytes"}`` one call of *kernel* requires, or ``None``
    for a kernel this file has no rule for."""
    if kernel not in _FLASH_TENSORS:
        return None
    rows = batch * model["n_heads"] * seq_len
    tensors, stats = _FLASH_TENSORS[kernel]
    return {"flops": 2.0 * rows * seq_len * model["head_dim"],
            "bytes": float(tensors * rows * model["head_dim"] * 2
                           + stats * rows * 4)}


def peaks(device_kind: str):
    """``{"flops_bf16", "hbm_bytes_per_s"}`` of one chip of
    *device_kind* by ``peaks.json``, or ``None``."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        return json.load(f)["by_device_kind"].get(device_kind)


def least_seconds(work: dict, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take for
    *work*, the larger of operations over peak FLOP/s and bytes over peak
    bytes/s, and which of the two it was."""
    compute = work["flops"] / peak["flops_bf16"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
