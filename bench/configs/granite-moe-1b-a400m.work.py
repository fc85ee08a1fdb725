"""Operations and bytes that granite-moe-1b-a400m needs, counted from the
configuration and the tokens actually processed: routed experts only
(tokens x top-k), attention over each sequence's real context, nothing
recomputed, never the program's padded or capacity shapes.  Bytes are
bf16 (2 per element)."""
from __future__ import annotations

BYTES = 2


def _sizes(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, h, c["num_key_value_heads"], d // h,
            c["intermediate_size"], c["num_local_experts"],
            c["num_experts_per_tok"], c["vocab_size"])


def prefill(c, n: int) -> dict:
    """One prompt of ``n`` tokens: the whole model (the head on the last
    position only, as served) and the two kernels' shares."""
    L, d, H, KV, hd, f, E, k, V = _sizes(c)
    proj = 2 * n * d * (2 * H * hd + 2 * KV * hd)
    attn = 2 * 2 * H * hd * n * (n + 1) // 2      # QK^T and PV, causal
    router = 2 * n * d * E
    experts = n * k * 3 * 2 * d * f
    flops = L * (proj + attn + router + experts) + 2 * d * V
    return {"flops": flops,
            "flash_attention": {
                "flops": L * attn,
                "bytes": L * BYTES * n * (2 * H * hd + 2 * KV * hd)},
            "moe_expert_ffn": expert_kernel(c, n)}


def decode(c, contexts) -> dict:
    """One decode step: one new token for each lane, lane i attending
    ``contexts[i]`` cached positions (its own included)."""
    L, d, H, KV, hd, f, E, k, V = _sizes(c)
    b = len(contexts)
    proj = 2 * b * d * (2 * H * hd + 2 * KV * hd)
    attn = 2 * 2 * H * hd * sum(contexts)
    router = 2 * b * d * E
    experts = b * k * 3 * 2 * d * f
    flops = L * (proj + attn + router + experts) + b * 2 * d * V
    return {"flops": flops, "moe_expert_ffn": expert_kernel(c, b)}


def expert_kernel(c, tokens: int) -> dict:
    """The expert FFN over ``tokens`` routed top-k: operations of the
    routed rows; bytes of the experts they reach (expected under uniform
    routing) and of the rows in and out."""
    L, d, H, KV, hd, f, E, k, V = _sizes(c)
    reached = E * (1.0 - (1.0 - k / E) ** tokens)
    return {"flops": L * tokens * k * 3 * 2 * d * f,
            "bytes": L * BYTES * (reached * 3 * d * f + 2 * tokens * k * d)}
