"""The program's parameters for granite-moe-1b-a400m, from the
benchmark's weights (``init_weights`` in ``granite-moe-1b-a400m.ref.py``).

The program's block is a plain MoE block: no embedding, attention,
residual or logits multipliers, attention scaled by 1/sqrt(head size).
Each multiplier is a constant factor on one linear map, so it is folded
into that map's weights and the program computes the configured model:

* the embedding times ``embedding_multiplier`` (the residual stream then
  starts as granite's does);
* the query projection times ``attention_multiplier * sqrt(head size)``;
* the attention and expert output projections times
  ``residual_multiplier``;
* the final norm's weight over ``logits_scaling``, and over
  ``embedding_multiplier`` too where the head is the (scaled) embedding.

Each folded weight is rounded once more to its type.
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def scaled(x, factor):
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


def weights(c, w):
    emb, res = c["embedding_multiplier"], c["residual_multiplier"]
    hd = c["hidden_size"] // c["num_attention_heads"]
    head = c["logits_scaling"] * (emb if c["tie_word_embeddings"] else 1.0)
    b = w["blocks"][0]
    attn = {**b["attn"],
            "wq": scaled(b["attn"]["wq"],
                         c["attention_multiplier"] * math.sqrt(hd)),
            "wo": scaled(b["attn"]["wo"], res)}
    moe = {**b["moe"], "w2": scaled(b["moe"]["w2"], res)}
    return {**w, "embed": scaled(w["embed"], emb),
            "final_norm": scaled(w["final_norm"], 1.0 / head),
            "blocks": [{**b, "attn": attn, "moe": moe}]}
