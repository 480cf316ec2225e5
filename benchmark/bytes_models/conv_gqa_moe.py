"""Bytes a gated short-convolution / grouped-query-attention decoder with
routed experts must move, from shapes alone. The yardstick of
``step.roofline_share``, ``conv.step_roofline_share`` and
``conv.page_roofline_share``: a later PR cannot change what a program is
held to.

A fused step of 32 rows and a 16-token page are both bound by memory
bandwidth (32 tokens against 2 FLOPs a parameter read is under the chip's
FLOP/s-to-bytes/s ratio of 240, and a touched expert is applied to every
row), so the least time is bytes over peak bandwidth. What is counted is
what the program reads in the type it holds it: projections, the
convolution's taps, experts and the embedding (which is the head too) in
``torch_dtype``; gains, the router and the carry (each convolution layer's
last ``conv_L_cache - 1`` products) in float32; K and V of a context in
``torch_dtype`` over the attention layers alone. The carry is read AND
written by every step and every page, so it counts twice. A context is
counted a seat: the pages of a shared prompt once for every seat that
attends to them.

``decode_step_bytes`` is a true least: every row may choose the same
``num_experts_per_tok`` experts in every layer, and a step has one seat at
least. ``step_bytes_counted`` and ``page_bytes_counted`` take the distinct
(layer, expert) pairs the program counted and the seats it stepped.
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


def _kept(conf: dict) -> list:
    return conf["layer_types"][:conf["num_hidden_layers"]]


def conv_layers(conf: dict) -> int:
    return _kept(conf).count("conv")


def attn_layers(conf: dict) -> int:
    return _kept(conf).count("full_attention")


def expert_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["num_dense_layers"]


def head_dim(conf: dict) -> int:
    return int(conf.get("head_dim")
               or conf["hidden_size"] // conf["num_attention_heads"])


def expert_bytes(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * conf["hidden_size"] * conf["moe_intermediate_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def fixed_weight_bytes(conf: dict) -> int:
    """Every weight a token step reads whatever it routes: the convolution
    and attention layers, the dense layers, each expert layer's router and
    selection bias, the gains, and the embedding as the head. Of the
    embedding as a table only the rows looked up (left out: a few KB)."""
    D, V = conf["hidden_size"], conf["vocab_size"]
    L, K = conf["num_hidden_layers"], conf["num_dense_layers"]
    hd = head_dim(conf)
    Hq, KVd = conf["num_attention_heads"] * hd, (
        conf["num_key_value_heads"] * hd)
    w = DTYPE_BYTES[conf["torch_dtype"]]
    conv = (D * 3 * D + conf["conv_L_cache"] * D + D * D) * w
    attn = (D * Hq + 2 * D * KVd + Hq * D) * w + 2 * hd * 4
    dense = 3 * D * conf["intermediate_size"] * w
    router = (D + 1) * conf["num_experts"] * 4
    return (conv_layers(conf) * conv + attn_layers(conf) * attn
            + L * 2 * D * 4 + K * dense + (L - K) * router + D * 4
            + V * D * w)


def weight_bytes(conf: dict) -> int:
    """All the weights the chip holds (PERF.md's sizes): the embedding is
    the head, held once."""
    return (fixed_weight_bytes(conf)
            + expert_layers(conf) * conf["num_experts"] * expert_bytes(conf))


def kv_bytes_per_token(conf: dict, dtype: str | None = None) -> int:
    """K and V of one position over the attention layers, in the type they
    are read in (the model's own in a step; the store's in a page)."""
    return (attn_layers(conf) * 2 * conf["num_key_value_heads"]
            * head_dim(conf) * DTYPE_BYTES[dtype or conf["torch_dtype"]])


def page_bytes(conf: dict, page_tokens: int, store_dtype: str = "float32") -> int:
    return page_tokens * kv_bytes_per_token(conf, store_dtype)


def carry_bytes(conf: dict) -> int:
    """One session's carry, which is also one prefix extent's snapshot: the
    last ``conv_L_cache - 1`` products of every convolution layer,
    float32."""
    return (conv_layers(conf) * (conf["conv_L_cache"] - 1)
            * conf["hidden_size"] * 4)


def step_bytes_counted(conf: dict, context_tokens: float, expert_rows: float,
                       seats: float) -> float:
    """One fused step of ``seats`` sessions that read ``expert_rows``
    distinct (layer, expert) pairs over contexts of ``context_tokens``
    positions in all: each seat's carry read and written. Other writes (one
    position's K and V a session, the logits) are left out."""
    return (fixed_weight_bytes(conf) + expert_rows * expert_bytes(conf)
            + context_tokens * kv_bytes_per_token(conf)
            + 2 * seats * carry_bytes(conf))


def page_bytes_counted(conf: dict, context_tokens: float,
                       expert_rows: float) -> float:
    """One page program: the same weights once a page, the experts it
    counted, its context's K and V, one session's carry in and out."""
    return step_bytes_counted(conf, context_tokens, expert_rows, 1)


def decode_step_bytes(conf: dict, context_tokens: float) -> float:
    """The least one fused step must move: every row may choose the same
    ``num_experts_per_tok`` experts in every layer, and a step has one seat
    at least."""
    return step_bytes_counted(
        conf, context_tokens,
        expert_layers(conf) * conf["num_experts_per_tok"], 1)
