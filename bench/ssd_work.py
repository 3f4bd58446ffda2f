"""Work of the Mamba-2 SSD core in one training step, from shapes: the
FLOPs and the least HBM traffic any implementation of the chunked SSD
has to spend, for the ``ssd_*`` readers.

The core is what the program's ``ssd`` scope holds: from x, dt, B and C
(after the projections, the conv and the softplus) to y (before D.x, the
gate and the output projection).  Its FLOPs per token and layer are the
core term of ``bench/flops.ssd_layer``: intra-chunk C.B^T and its product
with x over the causal half of a chunk, the state read-out and the state
update.  Its least traffic per token and layer is reading x, dt, B and C
once and writing y once, at the dtypes the step hands the core and takes
back: x, B and C in the compute dtype, dt and y in float32; the state
between chunks and every intra-chunk tile can stay on chip.  Training is
three times the forward pass, as in ``bench/flops.py``; recomputation by
remat is not counted.
"""
from __future__ import annotations

from typing import Any, Dict

F32_BYTES = 4


def _dims(c: Dict[str, Any]):
    m = c["mamba2_layer"]
    d_in = m["expand"] * c["d_model"]
    return (d_in // m["headdim"], m["headdim"], m["ngroups"], m["d_state"],
            m["chunk_size"])


def core_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward FLOPs of one layer's chunked SSD core for one token."""
    H, P, G, N, L = _dims(c)
    return 2 * (L / 2) * G * N + 2 * (L / 2) * H * P + 2 * 2 * H * N * P


def core_bytes_per_token(c: Dict[str, Any], compute_bytes: int) -> float:
    """Least HBM bytes of one layer's SSD core for one token, forward:
    x (H*P) and B, C (G*N each) in the compute dtype, dt (H) and y (H*P)
    in float32."""
    H, P, G, N, _ = _dims(c)
    return compute_bytes * (H * P + 2 * G * N) + F32_BYTES * (H + H * P)


def train_flops(c: Dict[str, Any], batch: int, seq: int) -> float:
    """FLOPs of the SSD core in one training step, all layers."""
    return 3.0 * batch * seq * c["n_layer"] * core_flops_per_token(c)


def train_bytes(c: Dict[str, Any], batch: int, seq: int,
                compute_bytes: int) -> float:
    """Least HBM bytes of the SSD core in one training step, all layers."""
    return (3.0 * batch * seq * c["n_layer"]
            * core_bytes_per_token(c, compute_bytes))
