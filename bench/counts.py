"""Operation and byte counts of the served models, from their sizes.

The benchmark's own arithmetic: the per-layer metrics divide these by
measured times, so no change to the program can move the yardstick.
Sizes come from the configuration file's ``dims`` (see ``model.py``).
"""
from __future__ import annotations

BF16 = 2


def layer_params(d: dict) -> dict:
    """Parameters of one decoder layer, by part."""
    q = d["d_model"] * d["n_heads"] * d["head_dim"]
    kv = d["d_model"] * d["n_kv_heads"] * d["head_dim"]
    attn = 2 * q + 2 * kv
    if d.get("qkv_bias"):
        attn += d["n_heads"] * d["head_dim"] + 2 * d["n_kv_heads"] * \
            d["head_dim"]
    return {"attn": attn, "ffn": 3 * d["d_model"] * d["d_ff"],
            "norms": 2 * d["d_model"]}


def param_count(d: dict) -> dict:
    """Every parameter of the model, by part; ``total`` sums them."""
    per = layer_params(d)
    layers = d["n_layers"] * sum(per.values())
    embed = d["vocab"] * d["d_model"]
    head = 0 if d.get("tie_embeddings") else d["vocab"] * d["d_model"]
    out = {"layers": layers, "embed": embed, "head": head,
           "final_norm": d["d_model"]}
    out["total"] = sum(out.values())
    return out


def matmul_params(d: dict) -> int:
    """Parameters a token multiplies: every layer's projections and the
    output head. The embedding is a row gather, not a multiply."""
    per = layer_params(d)
    head = d["vocab"] * d["d_model"]
    return d["n_layers"] * (per["attn"] + per["ffn"]) + head


def flops_per_token(d: dict) -> int:
    """2 x the parameters a token multiplies (attention over the context
    left out, so a share of a peak built on it is a lower bound)."""
    return 2 * matmul_params(d)


def kv_bytes_per_position(d: dict) -> int:
    """Cached keys and values of one position, every layer, in bf16."""
    return d["n_layers"] * 2 * d["n_kv_heads"] * d["head_dim"] * BF16


def decode_step_bytes(d: dict, context_positions: int) -> int:
    """Least HBM bytes one batched decode step moves: every weight once
    in bf16 (the configuration's compute type; the embedding contributes
    one row per sequence, counted as nothing) plus the cached keys and
    values of ``context_positions`` positions summed over the batch."""
    pc = param_count(d)
    weights = (pc["total"] - pc["embed"]) * BF16
    return weights + context_positions * kv_bytes_per_position(d)
