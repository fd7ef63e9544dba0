"""Operations the work requires, computed from shapes.

These counts are what the algorithm needs, not what an implementation
happens to compute: recomputation under rematerialisation, the masked half
of a full attention square and padding do not count.  Utilisation and
roofline shares divide them by measured time.
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


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: attention and
    MLP projections of every layer and the LM head.  The embedding lookup
    is a gather, and norms and biases are not products."""
    d, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * H * hd * 2 + d * KV * hd * 2
    mlp = 3 * d * f
    return cfg["num_hidden_layers"] * (attn + mlp) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward and backward FLOP per trained token of a dense decoder:
    6 x matmul parameters, plus causal attention counted once,
    6 x layers x seq x heads x head_dim (QK^T and PV over on average
    seq/2 keys, 2 FLOP a multiply-add, 3 for forward and backward)."""
    attn = 6 * cfg["num_hidden_layers"] * seq_len * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    return 6 * matmul_params(cfg) + attn


def flash_attention_fwd(B: int, H: int, KV: int, S: int, hd: int, *,
                        causal: bool = True, bytes_per_el: int = 2) -> tuple:
    """(FLOP, HBM bytes) that one forward call of a flash-attention kernel
    needs: QK^T and PV over the causal half (or the whole square), and
    reading q, k, v and writing o once."""
    pairs = S * (S + 1) / 2 if causal else S * S
    flop = 4 * B * H * hd * pairs
    byts = bytes_per_el * B * S * hd * (2 * H + 2 * KV)
    return flop, byts
