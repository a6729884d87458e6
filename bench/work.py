"""Operations and bytes a step needs, computed from shapes alone.

Every count here is what the algorithm requires, not what a program happens
to execute: attention over the tokens a row holds (not a padded buffer), one
pass over each weight. The keys are those of a Hugging Face ``config.json``
(``hidden_size``, ``num_attention_heads``, ...), so the counts follow the
configuration file a cell runs and nothing in the program under test.
"""

from __future__ import annotations

from collections.abc import Iterable


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through: Q/K/V/O and the gated MLP of
    every layer, and the LM head (the tied embedding counts once, as the
    head; the embedding lookup is a gather, not a matmul)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def attention_flops(cfg: dict, context: int) -> int:
    """QK^T and PV of one query token over ``context`` tokens, all layers."""
    return 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * head_dim(cfg) * context


def token_flops(cfg: dict, position: int) -> int:
    """Model FLOPs of the token at ``position`` (0-based): every matmul
    weight once (multiply + add) and attention over positions 0..position."""
    return 2 * matmul_params(cfg) + attention_flops(cfg, position + 1)


def span_flops(cfg: dict, start: int, n: int) -> int:
    """Model FLOPs of the ``n`` tokens at positions ``start..start+n-1``."""
    if n <= 0:
        return 0
    contexts = n * (start + 1) + n * (n - 1) // 2      # sum of position + 1
    return 2 * matmul_params(cfg) * n + attention_flops(cfg, contexts)


def decode_attention_flops(n_q_heads: int, dh: int, lengths: Iterable[int]) -> int:
    """One decode-attention call (one layer) over rows of ``lengths`` tokens."""
    return 4 * n_q_heads * dh * sum(int(n) for n in lengths)


def kv_bytes(n_kv_heads: int, dh: int, itemsize: int, lengths: Iterable[int]) -> int:
    """K and V bytes one layer's attention must read for rows of ``lengths``."""
    return 2 * n_kv_heads * dh * itemsize * sum(int(n) for n in lengths)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
