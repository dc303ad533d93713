"""Model operations of the work a cell completes, from a configuration file.

Multiply-add = 2. Matmul parameters are the weights of every projection and
of the output head (the embedding is a gather). Attention counts q.k and p.v
over the causal pairs. Training counts the forward and backward passes
(3x the forward) and nothing recomputed.
"""
from __future__ import annotations


def matmul_params(cfg):
    d, H, KV, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def _attn_pairs_flops(cfg, pairs):
    """q.k and p.v over ``pairs`` (query, key) pairs, in every layer."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs * cfg["num_hidden_layers"]


def train_flops(cfg, rows, seq_len):
    """Forward and backward of ``rows`` causal sequences of ``seq_len``."""
    tokens = rows * seq_len
    fwd = 2.0 * matmul_params(cfg) * tokens
    fwd += _attn_pairs_flops(cfg, rows * seq_len * (seq_len + 1) / 2)
    return 3.0 * fwd


def serve_flops(cfg, requests):
    """Prefill of each true prompt, then one decode step per further output
    token, for [(prompt length, output tokens)]."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    layers = matmul_params(cfg) - head
    total = 0.0
    for L, n in requests:
        # the layers see the prompt and then each output token but the last
        # (decode steps at positions L .. L + n - 2); the head runs once per
        # output token
        total += 2.0 * layers * (L + n - 1) + 2.0 * head * n
        pairs = L * (L + 1) / 2 + sum(L + 1 + j for j in range(n - 1))
        total += _attn_pairs_flops(cfg, pairs)
    return total
