"""Model FLOPs per token from a configuration's widths.

The PaLM count (Chowdhery et al. 2022, appendix B): `6 N + 12 L H Dh S` for
one forward and backward pass of a decoder-only transformer, where N is every
parameter (a tied embedding counted once), L the layers, H the query heads,
Dh the head size and S the sequence length. Recomputation is not counted.
The vocabulary is counted as the program lays it out, rounded up to a
multiple of `VOCAB_MULTIPLE`, since the output matmul runs over every row.
"""

from __future__ import annotations

VOCAB_MULTIPLE = 256


def padded_vocab(cfg: dict) -> int:
    v = int(cfg["vocab_size"])
    return -(-v // VOCAB_MULTIPLE) * VOCAB_MULTIPLE


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"])


def param_count(cfg: dict) -> int:
    """Parameters of a dense decoder-only LM with gated FFN, no biases."""
    d, f, dh = int(cfg["d_model"]), int(cfg["d_ff"]), head_dim(cfg)
    hq, hkv = int(cfg["num_heads"]), int(cfg["num_kv_heads"])
    attn = d * hq * dh * 2 + d * hkv * dh * 2
    ffn = 3 * d * f
    norms = 2 * d if cfg["parametric_norm"] else 0
    per_layer = attn + ffn + norms
    embed = padded_vocab(cfg) * d * (1 if cfg["tie_embeddings"] else 2)
    final_norm = d if cfg["parametric_norm"] else 0
    return int(cfg["num_layers"]) * per_layer + embed + final_norm


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * int(cfg["num_layers"]) * int(cfg["num_heads"]) * head_dim(cfg) * seq_len
    return 6.0 * param_count(cfg) + attn
