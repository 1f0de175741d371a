"""The yardstick's arithmetic: model FLOPs per token, the aggregation's
required bytes, and the chip's peaks.

Everything here is computed from the configuration file's numbers, never
from the program, so a later change to the program cannot move it.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def matmul_params(m: dict) -> int:
    """Weights that multiply an activation once per token per application:
    every layer's projections (a shared layer counts once per application)
    and the output head. Embedding lookups do no matmul."""
    d = m["d_model"]
    per_layer = 0
    for mixer, mlp, _cross in m["pattern"]:
        if mixer == "attn_full":
            q = m["n_heads"] * m["head_dim"]
            kv = m["n_kv_heads"] * m["head_dim"]
            per_layer += d * q + 2 * d * kv + q * d
        elif mixer == "ssm":
            di = m["ssm_expand"] * d
            h = di // m["ssm_head_dim"]
            per_layer += d * (2 * di + 2 * m["ssm_state"] + h) + di * d
        else:
            raise ValueError(f"no FLOP count for mixer {mixer!r}")
        if mlp == "dense":
            per_layer += d * m["d_ff"] * (3 if m["glu"] else 2)
        elif mlp != "none":
            raise ValueError(f"no FLOP count for mlp {mlp!r}")
    return per_layer * m["n_repeats"] + d * m["vocab_size"]


def sequence_flops(m: dict, seq: int) -> int:
    """Forward and backward FLOPs per token beyond the weight matmuls.

    Attention: 12 * heads * head_dim * seq per layer (scores and values,
    forward and backward, over the whole seq x seq matrix as computed).
    SSD (Mamba-2's chunked dual form, chunk Q = min(chunk, seq), n_groups 1):
    3 * (2 Q N + 2 Q H P + 4 N H P) per layer: C.B within the chunk, the
    masked chunk matrix times x, the chunk states and their read-out."""
    per_layer = 0
    for mixer, _mlp, _cross in m["pattern"]:
        if mixer == "attn_full":
            per_layer += 12 * m["n_heads"] * m["head_dim"] * seq
        elif mixer == "ssm":
            di = m["ssm_expand"] * m["d_model"]
            P, N = m["ssm_head_dim"], m["ssm_state"]
            H = di // P
            Q = min(m["ssm_chunk"], seq)
            per_layer += 3 * (2 * Q * N + 2 * Q * H * P + 4 * N * H * P)
    return per_layer * m["n_repeats"]


def flops_per_token(m: dict, seq: int) -> int:
    """Model FLOPs of one training token: 6 per matmul weight plus the
    sequence terms; rematerialised work is not counted."""
    return 6 * matmul_params(m) + sequence_flops(m, seq)


def aggregation_bytes(n_peers: int, d: int, n_iters: int,
                      wire_bytes: int = 4) -> int:
    """HBM bytes one owner's CenteredClip aggregation needs: ``n_iters``
    passes and one digest pass over its n x ceil(d/n) stack in the wire
    dtype, plus writing the f32 aggregate (kernels/DESIGN.md's pass model)."""
    part = -(-d // n_peers)
    return (n_iters + 1) * n_peers * part * wire_bytes + part * 4


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]
