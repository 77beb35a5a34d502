"""Operations and bytes the algorithm needs, from shapes alone.

Matmul FLOPs are 2 per multiply-add. Attention is counted causal (a query at
position i meets i + 1 keys). Recomputed operations never count."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that sit in a matrix product on the forward pass: the four
    block projections of every layer and the tied LM head. `wpe`, biases and
    layer norms multiply nothing."""
    e, layers, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    hidden = cfg.get("mlp_ratio", 4) * e
    return layers * (3 * e * e + e * e + 2 * e * hidden) + e * vocab


def attention_forward_flops(cfg: dict, context: float) -> float:
    """One token's QK^T and PV over `context` keys, all layers."""
    return 4.0 * cfg["n_layer"] * cfg["n_embd"] * context


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (2x forward) of one token in a causal sequence
    of `seq`: the mean context is (seq + 1) / 2."""
    return 3.0 * (2.0 * matmul_params(cfg) + attention_forward_flops(cfg, (seq + 1) / 2))


def serve_request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one request needs: every prompt token and every fed-back
    token through the blocks, the LM head once per token produced."""
    e, vocab = cfg["n_embd"], cfg["vocab_size"]
    body = 2.0 * (matmul_params(cfg) - e * vocab)
    fed = prompt_len + max(new_tokens - 1, 0)  # the last token is never fed back
    # token at position i (0-based) meets i + 1 keys
    context_sum = fed * (fed + 1) / 2.0
    return fed * body + attention_forward_flops(cfg, 1.0) * context_sum + new_tokens * 2.0 * e * vocab


def flash_attention_train_cost(cfg: dict, batch: int, seq: int, bytes_per_el: int = 2) -> dict:
    """Causal flash attention, forward and backward, all layers of one step.

    Forward: QK^T and PV. Backward: S again (the algorithm keeps no scores),
    dV, dP, dQ, dK: 2.5x the forward. Bytes: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o, do and writes dq, dk, dv."""
    heads, head_dim, layers = cfg["n_head"], cfg["n_embd"] // cfg["n_head"], cfg["n_layer"]
    fwd = 4.0 * batch * heads * head_dim * seq * (seq + 1) / 2.0
    tensor = batch * seq * heads * head_dim * bytes_per_el
    return {"flops": layers * 3.5 * fwd, "bytes": layers * (4 + 8) * tensor}


def paged_decode_cost(cfg: dict, live_tokens: float, rows: int, kv_bytes_per_el: int = 2) -> dict:
    """One call of the single-query paged kernel in one layer: `live_tokens`
    keys and values summed over the `rows` slots."""
    e = cfg["n_embd"]
    return {"flops": 4.0 * e * live_tokens,
            "bytes": 2.0 * e * live_tokens * kv_bytes_per_el + 2.0 * rows * e * kv_bytes_per_el}


def roofline_seconds(cost: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s."""
    return max(cost["flops"] / peaks["bf16_flops_per_s"], cost["bytes"] / peaks["hbm_bytes_per_s"])
