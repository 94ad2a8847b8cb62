"""Operations and bytes a dense decoder requires, from its shapes.

The benchmark's own copy of the shape arithmetic: what the model needs,
not what an implementation happens to do.  A pruned FFN counts only its
kept units; causal attention counts only the positions a token attends;
recomputation counts nothing.
"""
from __future__ import annotations


def token_flops(dm: dict, context: int, ff_kept: int) -> float:
    """Forward FLOPs of one token that attends ``context`` positions."""
    d, h, kv, hd = dm["d"], dm["h"], dm["kv"], dm["hd"]
    proj = 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
    attn = 4 * h * hd * context
    ffn = 2 * d * ff_kept * 3
    return dm["L"] * (proj + attn + ffn) + 2 * d * dm["V"]


def sequence_flops(dm: dict, seq: int, ff_kept: int) -> float:
    """Forward FLOPs of one causal sequence of ``seq`` tokens."""
    d, h, kv, hd = dm["d"], dm["h"], dm["kv"], dm["hd"]
    per_token = token_flops(dm, 0, ff_kept)
    attn = dm["L"] * 4 * h * hd * seq * (seq + 1) / 2
    return seq * per_token + attn


def train_round_flops(dm: dict, hp: dict, ff_kept: int) -> float:
    """Forward and backward (3x forward) of every client and server
    sequence of one federated round."""
    seqs = (hp["clients_per_round"] * hp["local_steps"] * hp["batch_size"]
            + hp["tau"] * hp["server_batch_size"])
    return 3 * seqs * sequence_flops(dm, hp["row_shape"][0], ff_kept)


def eval_flops(dm: dict, hp: dict, ff_kept: int) -> float:
    return hp["test_rows"] * sequence_flops(dm, hp["row_shape"][0], ff_kept)


def decode_attention_work(dm: dict, contexts, bytes_per: int = 2):
    """(FLOPs, bytes) one decode-attention call requires for one layer:
    per slot, q against its ``context`` valid K/V rows, and the output."""
    h, kv, hd = dm["h"], dm["kv"], dm["hd"]
    flops = sum(4 * h * hd * c for c in contexts)
    byts = sum((2 * h * hd + 2 * c * kv * hd) * bytes_per for c in contexts)
    return flops, byts
