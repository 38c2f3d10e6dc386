"""Operations and bytes of the model's steps and kernels, from their shapes.

Counts are of the work the computation needs, whatever implements it: a
multiply-add is 2 operations, a causal attention call counts only the
(query, key) pairs at or below the diagonal, and bytes are those of the
call's inputs and outputs read or written once.  ``cfg`` is a configuration
file (Hugging Face key names).
"""

from __future__ import annotations


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o and SwiGLU)."""
    d, H, K, hd, ff, _, _ = _dims(cfg)
    return d * hd * (H + 2 * K) + H * hd * d + 3 * d * ff


def attention_pair_flops(cfg: dict) -> int:
    """Operations per (query, key) pair in one layer: q.k and p.v for every
    query head."""
    _, H, _, hd, _, _, _ = _dims(cfg)
    return 4 * H * hd


def prefill_flops(cfg: dict, n: int) -> float:
    """Model operations of a prefill of ``n`` real prompt tokens, with the
    logits taken at one position (as the serve path does)."""
    d, _, _, _, _, V, L = _dims(cfg)
    pairs = n * (n + 1) / 2
    return (2.0 * n * L * layer_matmul_params(cfg)
            + L * attention_pair_flops(cfg) * pairs + 2.0 * d * V)


def decode_flops(cfg: dict, contexts) -> float:
    """Model operations of one decode step that extends sequences whose
    attention spans are ``contexts`` (keys attended, new token included)."""
    d, _, _, _, _, V, L = _dims(cfg)
    per_token = 2.0 * L * layer_matmul_params(cfg) + 2.0 * d * V
    return (len(contexts) * per_token
            + L * attention_pair_flops(cfg) * float(sum(contexts)))


def flash_call(cfg: dict, s: int, dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal attention call over ``s`` positions
    of one sequence: q, k, v read and o written once."""
    _, H, K, hd, _, _, _ = _dims(cfg)
    flops = attention_pair_flops(cfg) * s * (s + 1) / 2
    nbytes = dtype_bytes * s * hd * (2 * H + 2 * K)
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Least seconds the chip needs: the larger of the compute bound and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
