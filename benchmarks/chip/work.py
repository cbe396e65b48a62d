"""Work counts from shapes, and the table of peaks.

The counts are what the algorithm needs, whatever implements it: a LUT
matmul of live rows ``M`` does ``M*K*N`` table products (counted as two
integer operations each) and moves its operand codes at their width and
its int32 outputs once; a model step does two FLOPs per matmul parameter
per token, plus attention over the token's position.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    path = Path(path or PEAKS)
    table = json.loads(path.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table['devices'])}") from None


def lut_matmul_ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def lut_matmul_bytes(m: int, k: int, n: int, bits: int) -> float:
    """Operand codes at ``bits`` per code, plus int32 outputs."""
    return (m * k + k * n) * bits / 8 + 4 * m * n


def lut_matmul_floor(m: int, k: int, n: int, bits: int,
                     peak: dict) -> tuple[float, str]:
    """Least time of one call on the chip, and which bound sets it."""
    compute = lut_matmul_ops(m, k, n) / peak["int8_op_s"]
    memory = lut_matmul_bytes(m, k, n, bits) / peak["hbm_bytes_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def mlp_shapes(d_model: int, d_ff: int) -> list[tuple[int, int]]:
    """(K, N) of a SwiGLU MLP's three matmuls: up, gate, down."""
    return [(d_model, d_ff), (d_model, d_ff), (d_ff, d_model)]


def matmul_params(dims) -> int:
    """Parameters that a token multiplies: every layer's projections and
    MLP, and the head (the embedding lookup is not a matmul)."""
    D, H, Hkv, hd, F = (dims.d_model, dims.heads, dims.kv_heads,
                        dims.head_dim, dims.d_ff)
    per_layer = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F
    return dims.layers * per_layer + D * dims.vocab


def flops_per_token(dims, pos: int) -> int:
    """Model FLOPs of one token at position ``pos`` (0-based): two per
    matmul parameter, plus QK^T and PV over the ``pos + 1`` keys it sees."""
    attn = 4 * dims.heads * dims.head_dim * (pos + 1) * dims.layers
    return 2 * matmul_params(dims) + attn
