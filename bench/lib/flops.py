"""Operations and bytes of the model's work, from its sizes alone.

Operations count multiply-adds as two. Only the model's mathematics
counts: recomputation, padding and masked-out attention do not.
"""

from __future__ import annotations


def _dims(model: dict):
    d, H, Hkv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    dh = model.get("head_dim") or d // H
    return model["num_layers"], d, H, Hkv, dh, model["d_ff"], model["vocab_size"]


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and feed-forward, and the output head (the
    embedding is a lookup)."""
    L, d, H, Hkv, dh, F, V = _dims(model)
    per_layer = d * H * dh + 2 * d * Hkv * dh + H * dh * d + 3 * d * F
    return L * per_layer + V * d


def attended(pos: int, window: int | None) -> int:
    """Keys that the query at 0-based ``pos`` attends to."""
    return min(pos + 1, window) if window else pos + 1


def attended_sum(start: int, stop: int, window: int | None) -> int:
    """Keys attended by the queries at positions ``[start, stop)``."""
    def upto(n):  # sum over pos in [0, n)
        if not window or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(stop) - upto(start)


def attention_flops_per_key(model: dict) -> int:
    """Scores plus weighted values, per query and attended key, all layers."""
    L, _, H, _, dh, _, _ = _dims(model)
    return 4 * L * H * dh


def forward_flops(model: dict, start: int, stop: int) -> int:
    """Forward operations for the tokens at positions ``[start, stop)`` of
    one sequence whose earlier positions are cached."""
    n = stop - start
    window = model.get("sliding_window")
    return (2 * matmul_params(model) * n
            + attention_flops_per_key(model) * attended_sum(start, stop, window))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token (three times the
    forward) of a causal sequence of ``seq_len``."""
    return 3 * forward_flops(model, 0, seq_len) / seq_len


def param_count(model: dict) -> int:
    """Every parameter: the matrix products', the embedding (unless the
    head shares it) and the norm scales (two a layer and a final one)."""
    L, d, _, _, _, _, V = _dims(model)
    embed = 0 if model.get("tie_embeddings") else V * d
    return matmul_params(model) + embed + (2 * L + 1) * d


def decode_step_flops(model: dict, positions: list[int]) -> int:
    """Operations of one batched decode step in which a row at each of
    ``positions`` emits a token."""
    return sum(forward_flops(model, p, p + 1) for p in positions)


def decode_step_bytes(model: dict, positions: list[int], bytes_per_weight: float,
                      bytes_per_kv_value: int = 2) -> float:
    """The least bytes that one batched decode step with a row at each of
    ``positions`` reads: every weight once, but of an embedding table that
    the head does not share only the rows of the batch's tokens; and the
    cached keys and values of the earlier positions that each row attends
    to (the current position's are computed, not read)."""
    L, d, _, _, _, _, V = _dims(model)
    weights = param_count(model)
    if not model.get("tie_embeddings"):
        weights -= V * d - len(positions) * d
    window = model.get("sliding_window")
    kv = sum(attended(p, window) - 1 for p in positions)
    return weights * bytes_per_weight + kv * kv_bytes_per_position(model, bytes_per_kv_value)


def kv_bytes_per_position(model: dict, bytes_per_value: int = 2) -> int:
    L, _, _, Hkv, dh, _, _ = _dims(model)
    return L * 2 * Hkv * dh * bytes_per_value
