"""Bytes a latent-attention, routed-expert, hyper-connection decoder must
move, from shapes alone. The yardstick of ``step.roofline_share``,
``moe.step_roofline_share`` and ``prefill.page_roofline_share``: a later PR
cannot change what a program is held to.

A fused step of 16 rows and a 16-token page are both bound by memory
bandwidth (16 tokens against 2 FLOPs a parameter read is far under the
chip's FLOP/s-to-bytes/s ratio), so the least time is bytes over peak
bandwidth. What is counted is what the program reads in the type it holds
it: projections, experts, embedding and head in ``torch_dtype``; gains, the
router and the hyper-connection coefficients in float32.

``decode_step_bytes`` is a true least: of the routed experts it counts
``num_experts_per_tok`` a layer, what a step reads when every row chooses
the same ones. ``step_bytes_counted`` and ``page_bytes_counted`` take the
distinct (layer, expert) pairs the program counted instead.
"""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_bytes_model_shared",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "bytes_model.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
DTYPE_BYTES = _shared.DTYPE_BYTES


def expert_bytes(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * conf["hidden_size"] * conf["moe_intermediate_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def expert_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def latent_width(conf: dict) -> int:
    """Values a position a layer holds in the cache."""
    return conf["kv_lora_rank"] + conf["qk_rope_head_dim"]


def fixed_weight_bytes(conf: dict) -> int:
    """Every weight a token step reads whatever it routes: attention, the
    hyper-connection coefficients, the dense layers, each expert layer's
    router and shared expert, the final gain and the head. Of the embedding
    only the rows looked up (left out: a few KB)."""
    D, H, V = (conf["hidden_size"], conf["num_attention_heads"],
               conf["vocab_size"])
    L, K = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    Rq, R = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    n = conf["hc_mult"]
    w = DTYPE_BYTES[conf["torch_dtype"]]
    attention = (D * Rq + Rq * H * (dn + dr) + D * (R + dr)
                 + R * H * (dn + dv) + H * dv * D) * w + (2 * D + Rq + R) * 4
    coeffs = 2 * (n * D + n * D * (2 * n + n * n) + (2 * n + n * n) + 3) * 4
    dense = 3 * D * conf["intermediate_size"] * w
    shared = (3 * D * conf["moe_intermediate_size"]
              * conf["n_shared_experts"] * w)
    router = (D + 1) * conf["n_routed_experts"] * 4
    return (L * (attention + coeffs) + K * dense
            + (L - K) * (shared + router) + D * 4 + D * V * w)


def weight_bytes(conf: dict) -> int:
    """All the weights the chip holds (PERF.md's sizes)."""
    return (fixed_weight_bytes(conf)
            + expert_layers(conf) * conf["n_routed_experts"]
            * expert_bytes(conf)
            + conf["vocab_size"] * conf["hidden_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def kv_bytes_per_token(conf: dict, dtype: str | None = None) -> int:
    """The latent of one position over all layers, in the type it is read
    in (the model's own in a step; the store's in a page)."""
    return (conf["num_hidden_layers"] * latent_width(conf)
            * DTYPE_BYTES[dtype or conf["torch_dtype"]])


def page_bytes(conf: dict, page_tokens: int, store_dtype: str = "float32") -> int:
    return page_tokens * kv_bytes_per_token(conf, store_dtype)


def step_bytes_counted(conf: dict, context_tokens: float,
                       expert_rows: float) -> float:
    """One fused step that read ``expert_rows`` distinct (layer, expert)
    pairs over contexts of ``context_tokens`` positions in all. Writes (one
    position's latent a session, the logits) are left out."""
    return (fixed_weight_bytes(conf) + expert_rows * expert_bytes(conf)
            + context_tokens * kv_bytes_per_token(conf))


def page_bytes_counted(conf: dict, context_tokens: float,
                       expert_rows: float) -> float:
    """One page program: the same weights once a page, the experts it
    counted, its context's latent."""
    return step_bytes_counted(conf, context_tokens, expert_rows)


def decode_step_bytes(conf: dict, context_tokens: float) -> float:
    """The least one fused step must move: every row may choose the same
    ``num_experts_per_tok`` experts in every layer."""
    return step_bytes_counted(
        conf, context_tokens,
        expert_layers(conf) * conf["num_experts_per_tok"])
