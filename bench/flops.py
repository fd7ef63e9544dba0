"""Operations the work requires, computed from shapes.

These counts are what the algorithm needs, not what an implementation
happens to compute: recomputation under rematerialisation, the masked half
of a full attention square and padding do not count.  Utilisation and
roofline shares divide them by measured time.  A configuration's FLOP per
trained token is its reference module's (``train_flops_per_token``); what
is here is the peaks table and the counts of kernels.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind`` (``peaks.json``); a kind
    that the table does not hold is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def flash_attention_fwd(B: int, H: int, KV: int, S: int, hd: int, *,
                        causal: bool = True, bytes_per_el: int = 2) -> tuple:
    """(FLOP, HBM bytes) that one forward call of a flash-attention kernel
    needs: QK^T and PV over the causal half (or the whole square), and
    reading q, k, v and writing o once."""
    pairs = S * (S + 1) / 2 if causal else S * S
    flop = 4 * B * H * hd * pairs
    byts = bytes_per_el * B * S * hd * (2 * H + 2 * KV)
    return flop, byts
