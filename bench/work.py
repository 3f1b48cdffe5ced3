"""Work counts: the operations and bytes each measured piece needs, from
the configuration's numbers alone, and the table of device peaks.

* Model FLOP per training token: the matrix products of the forward pass
  (projections, attention scores and values over the causal context,
  MLP, logits) times 3 for forward and backward; recomputation is not
  counted.  The arithmetic of the program's
  ``benchmarks/costmodel.py:forward_cost`` for dense attention blocks.
* The random-bases kernels: each contracts every compartment's
  d_c x Q_c basis with a vector, 2 * sum_c d_c * Q_c operations
  (unpadded; the K-worker reconstruction does it K times).  Generating
  the basis is not counted as needed work, so a stored basis, a
  hardware generator and Threefry are held to one yardstick.  Bytes:
  the packed gradient read (projection), the packed parameters read and
  written (reconstruction).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """{"flops": FLOP/s, "hbm_bytes": B/s} of one chip of this kind."""
    table = json.load(open(os.path.join(HERE, "peaks.json")))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def forward_flops_per_token(m: dict, seq: int) -> float:
    d, hd, h, kv = m["d_model"], m["d_head"], m["n_heads"], m["n_kv_heads"]
    per_layer = 2 * d * (2 * h * hd + 2 * kv * hd)      # q, k, v, o
    per_layer += 2 * 2 * (seq + 1) / 2 * h * hd         # causal scores + values
    per_layer += (3 if m["act"] == "silu" else 2) * 2 * d * m["d_ff"]
    return m["n_layers"] * per_layer + 2 * d * m["vocab"]


def train_flops_per_token(m: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq)


def sum_dq(dims, sizes) -> int:
    """sum_c d_c * Q_c over the compartments."""
    return int(sum(int(d) * int(q) for d, q in zip(dims, sizes)))


def project_work(dims, sizes, q_packed: int) -> tuple[float, float]:
    """(operations, bytes) of one projection launch."""
    return 2.0 * sum_dq(dims, sizes), 4.0 * q_packed


def reconstruct_work(dims, sizes, q_packed: int,
                     workers: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one reconstruct-apply launch."""
    return 2.0 * workers * sum_dq(dims, sizes), 8.0 * q_packed


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of compute and memory time."""
    t_ops, t_mem = ops / peak["flops"], nbytes / peak["hbm_bytes"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
