"""Operations and bytes of the benchmark's kernels and of a federated
round, computed from shapes alone, and the peak table they are held to.

Sources: the kernels' own definitions (``per_client_sign_align`` reads
the (C, R, 1024) f32 update arena and the (R, 1024) int8 reference sign
once and writes (R/8, C, 128) f32 partial counts; ``masked_agg`` reads
the arena and the (C,) weights once and writes one (R, 1024) f32
aggregate). A dense layer of fan-in a and fan-out b costs 2ab operations
per sample forward and 4ab backward, so a training sample costs 6P and
an evaluated one 2P, P counting the weights (the bias adds are left
out as elementwise work).
"""
from __future__ import annotations

import json
import math
import pathlib

LANE = 1024
BLOCK_R = 8
PART = 128
PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def sign_align(clients: int, rows: int) -> dict:
    """One ``per_client_sign_align`` call: sign, compare and count per
    element; bytes of the arena, the reference and the partials."""
    elems = clients * rows * LANE
    partials = math.ceil(rows / BLOCK_R) * clients * PART
    return {"ops": 3 * elems,
            "bytes": 4 * elems + rows * LANE + 4 * partials}


def masked_agg(clients: int, rows: int) -> dict:
    """One ``masked_agg`` call: a multiply-add per element of the arena;
    bytes of the arena, the weights and the aggregate."""
    elems = clients * rows * LANE
    return {"ops": 2 * elems,
            "bytes": 4 * elems + 4 * clients + 4 * rows * LANE}


KERNELS = {"sign_align": sign_align, "masked_agg": masked_agg}


def roofline_seconds(cost: dict, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound
    (at the bf16 peak) and the memory bound (at the HBM peak)."""
    return max(cost["ops"] / peak["bf16_flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])


def weight_count(dims) -> int:
    """Multiply-accumulate weights of an MLP with layer widths ``dims``."""
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def train_flops(dims, samples: int) -> float:
    return 6.0 * weight_count(dims) * samples


def eval_flops(dims, samples: int) -> float:
    return 2.0 * weight_count(dims) * samples
