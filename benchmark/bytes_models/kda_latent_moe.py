"""Bytes a delta-rule / latent-attention decoder with a share of its routed
experts must move, from shapes alone. The yardstick of
``step.roofline_share``, ``kda.step_roofline_share`` and
``kda.page_roofline_share``: a later PR cannot change what a program is
held to.

A fused step of 64 rows and a 16-token page are both bound by memory
bandwidth (64 tokens against 2 FLOPs a parameter read is under the chip's
FLOP/s-to-bytes/s ratio of 240, and a held expert is applied to every row),
so the least time is bytes over peak bandwidth. What is counted is what the
program reads in the type it holds it: projections, experts, embedding and
head in ``torch_dtype``; gains, the router, the convolution and the carry
(the delta rule's state and the convolution's last inputs) in float32. The
carry is read AND written by every step and every page, so it counts twice.

``decode_step_bytes`` is a true least: no held expert (every row may
choose experts that live on other chips) and one seat's carry.
``step_bytes_counted`` and ``page_bytes_counted`` take the distinct (layer,
held expert) pairs the program counted and the seats it stepped.
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


def latent_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] // conf["layer_group_size"]


def kda_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - latent_layers(conf)


def expert_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def expert_bytes(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * conf["hidden_size"] * conf["moe_intermediate_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def latent_width(conf: dict) -> int:
    """Values a position a latent layer holds in the cache."""
    return conf["kv_lora_rank"] + conf["qk_rope_head_dim"]


def fixed_weight_bytes(conf: dict) -> int:
    """Every weight a token step reads whatever it routes: both kinds of
    attention, the dense layers, each expert layer's router and shared
    expert, the gains and the head. Of the embedding only the rows looked
    up (left out: a few KB)."""
    D, H, V = (conf["hidden_size"], conf["num_attention_heads"],
               conf["vocab_size"])
    L, K = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    R, dn, dr, dv = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                     conf["qk_rope_head_dim"], conf["v_head_dim"])
    C = H * conf["head_dim"]
    w = DTYPE_BYTES[conf["torch_dtype"]]
    kda = ((D * 3 * C + D * C + D * 2 * H + C * D) * w
           + (conf["short_conv_kernel_size"] * 3 * C + H + C
              + conf["head_dim"]) * 4)
    mla = (D * H * (dn + dr) + D * (R + dr) + R * H * (dn + dv)
           + H * dv * D) * w + R * 4
    dense = 3 * D * conf["intermediate_size"] * w
    shared = 3 * D * conf["moe_shared_expert_intermediate_size"] * w
    router = (D + 1) * conf["router_experts"] * 4
    return (kda_layers(conf) * kda + latent_layers(conf) * mla + L * 2 * D * 4
            + K * dense + (L - K) * (shared + router) + D * 4 + D * V * w)


def weight_bytes(conf: dict) -> int:
    """All the weights the chip holds (PERF.md's sizes): ``num_experts``
    is the experts held here."""
    return (fixed_weight_bytes(conf)
            + expert_layers(conf) * conf["num_experts"] * expert_bytes(conf)
            + conf["vocab_size"] * conf["hidden_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def kv_bytes_per_token(conf: dict, dtype: str | None = None) -> int:
    """The latent of one position over the latent layers, in the type it is
    read in (the model's own in a step; the store's in a page)."""
    return (latent_layers(conf) * latent_width(conf)
            * DTYPE_BYTES[dtype or conf["torch_dtype"]])


def page_bytes(conf: dict, page_tokens: int, store_dtype: str = "float32") -> int:
    return page_tokens * kv_bytes_per_token(conf, store_dtype)


def carry_bytes(conf: dict) -> int:
    """One session's carry: a float32 state of (heads, head_dim, head_dim)
    and the convolution's last ``kernel - 1`` inputs, a KDA layer."""
    H, dk = conf["num_attention_heads"], conf["head_dim"]
    return kda_layers(conf) * 4 * (
        H * dk * dk + (conf["short_conv_kernel_size"] - 1) * 3 * H * dk)


def step_bytes_counted(conf: dict, context_tokens: float, expert_rows: float,
                       seats: float) -> float:
    """One fused step of ``seats`` sessions that read ``expert_rows``
    distinct (layer, held expert) pairs over contexts of ``context_tokens``
    positions in all: each seat's carry read and written. Other writes
    (one position's latent a session, the logits) are left out."""
    return (fixed_weight_bytes(conf) + expert_rows * expert_bytes(conf)
            + context_tokens * kv_bytes_per_token(conf)
            + 2 * seats * carry_bytes(conf))


def page_bytes_counted(conf: dict, context_tokens: float,
                       expert_rows: float) -> float:
    """One page program: the same weights once a page, the held experts it
    counted, its context's latent, one session's carry in and out."""
    return step_bytes_counted(conf, context_tokens, expert_rows, 1)


def decode_step_bytes(conf: dict, context_tokens: float) -> float:
    """The least one fused step must move: every row may choose experts
    that all live elsewhere, and a step has one seat at least."""
    return step_bytes_counted(conf, context_tokens, 0, 1)
