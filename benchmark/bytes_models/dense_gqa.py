"""Bytes a dense grouped-query decoder must move, from shapes alone. The
yardstick of ``step.roofline_share``: a later PR cannot change what a step
is held to.

A decode step of a dense decoder is bound by memory bandwidth at these batch
sizes (8 tokens against 2 * parameters FLOPs each is far under the chip's
FLOP/s-to-bytes/s ratio), so its least time is bytes over peak bandwidth.

A family's bytes model offers ``decode_step_bytes(conf, context_tokens)``;
what else is here is for this family's tests and for PERF.md's sizes. The
width of a type comes from the shared table in ``../bytes_model.py``.
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


def weight_bytes(conf: dict) -> int:
    """Every weight a token step reads once: the layers, the final norm, the
    output head, and nothing of the embedding table but the rows looked up
    (left out: a few KB). Norm gains are float32."""
    D, F = conf["hidden_size"], conf["intermediate_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = D // H
    L, V = conf["num_hidden_layers"], conf["vocab_size"]
    w = DTYPE_BYTES[conf["torch_dtype"]]
    per_layer = (D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F) * w
    return L * (per_layer + 2 * D * 4) + D * 4 + D * V * w


def embedding_bytes(conf: dict) -> int:
    return conf["vocab_size"] * conf["hidden_size"] * DTYPE_BYTES[
        conf["torch_dtype"]]


def kv_bytes_per_token(conf: dict, dtype: str | None = None) -> int:
    """K and V of one position over all layers, in the type they are read
    in (the model's own in a step; the store's in a page)."""
    hd = conf["hidden_size"] // conf["num_attention_heads"]
    w = DTYPE_BYTES[dtype or conf["torch_dtype"]]
    return 2 * conf["num_hidden_layers"] * conf["num_key_value_heads"] * hd * w


def page_bytes(conf: dict, page_tokens: int, store_dtype: str = "float32") -> int:
    return page_tokens * kv_bytes_per_token(conf, store_dtype)


def decode_step_bytes(conf: dict, context_tokens: int) -> int:
    """One fused step over a batch whose contexts hold ``context_tokens``
    positions in all: the weights once, every context position's K and V
    once. Writes (one position's K and V a session, the logits) are left
    out: under a thousandth of the reads."""
    return weight_bytes(conf) + context_tokens * kv_bytes_per_token(conf)
