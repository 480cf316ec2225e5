"""Bytes a window- and full-attention decoder with softmax-routed experts,
all held, no gate and no shared expert (Mellum2) must move, from shapes
alone. The yardstick of ``step.roofline_share``,
``family.step_roofline_share`` and ``family.page_roofline_share``: a later
PR cannot change what a program is held to.

A fused step of 16 rows and a 16-token page are both bound by memory
bandwidth (16 tokens against 2 FLOPs a parameter read is far under the
chip's FLOP/s-to-bytes/s ratio of 240, and an expert a token chose is read
whole), so the least time is bytes over peak bandwidth. What is counted is
what the program reads in the type it holds it: projections, experts,
embedding and head in ``torch_dtype``; gains and the router in float32;
the cache in ``torch_dtype`` (the page pool, the tails and a page
program's context hold it so).

The cache has two kinds: a full layer keeps every position, a sliding layer
the last ``sliding_window``. One (layer, position) pair is a K and a V of
``num_key_value_heads * head_dim`` values, whichever kind the layer is, so
a context is counted in such pairs: the program's ``kv.positions_held``
counter is that count over a fused step's seated sessions' live pages, and
``kv.page_positions_read`` over a page program's context.

``decode_step_bytes`` is a true least: one seat, the experts of one token
(``num_experts_per_tok`` a layer), the full layers over the context, the
sliding layers over one window of it. ``step_bytes_counted`` and
``page_bytes_counted`` take the distinct (layer, expert) pairs the program
counted, the (layer, position) pairs it read and the seats it stepped.
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


def _layers(conf: dict, value: str) -> int:
    return sum(conf["layer_types"][i] == value
               for i in range(conf["num_hidden_layers"]))


def full_layers(conf: dict) -> int:
    return _layers(conf, "full_attention")


def window_layers(conf: dict) -> int:
    return _layers(conf, "sliding_attention")


def expert_bytes(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * conf["hidden_size"] * conf["moe_intermediate_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def attention_bytes(conf: dict) -> int:
    """One layer's attention, whatever its kind: Wq, Wk, Wv and Wo."""
    D, hd = conf["hidden_size"], conf["head_dim"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    return (2 * D * H * hd + 2 * D * KV * hd) * DTYPE_BYTES[conf["torch_dtype"]]


def fixed_weight_bytes(conf: dict) -> int:
    """Every weight a token step reads whatever it routes: every layer's
    attention and router (float32), the gains and the head. Of the
    embedding only the rows looked up (left out: a few KB)."""
    D, V, L = conf["hidden_size"], conf["vocab_size"], conf["num_hidden_layers"]
    w = DTYPE_BYTES[conf["torch_dtype"]]
    router = D * conf["num_experts"] * 4
    return L * (attention_bytes(conf) + router + 2 * D * 4) + D * 4 + D * V * w


def weight_bytes(conf: dict) -> int:
    """All the weights the chip holds (PERF.md's sizes): every expert of
    every layer and the embedding."""
    return (fixed_weight_bytes(conf)
            + conf["num_hidden_layers"] * conf["num_experts"]
            * expert_bytes(conf)
            + conf["vocab_size"] * conf["hidden_size"]
            * DTYPE_BYTES[conf["torch_dtype"]])


def layer_position_bytes(conf: dict, dtype: str | None = None) -> int:
    """The K and the V of one position in one layer, in the type they are
    read in (the model's own in a step and a page program; the store's in
    a stored page)."""
    return (2 * conf["num_key_value_heads"] * conf["head_dim"]
            * DTYPE_BYTES[dtype or conf["torch_dtype"]])


def page_bytes(conf: dict, page_tokens: int,
               store_dtype: str = "float32") -> dict:
    """A stored page of each kind; the store is built for the larger."""
    one = page_tokens * layer_position_bytes(conf, store_dtype)
    return {"full": full_layers(conf) * one,
            "window": window_layers(conf) * one}


def step_bytes_counted(conf: dict, layer_positions: float,
                       expert_rows: float, seats: float,
                       page_tokens: int = 16) -> float:
    """One fused step of ``seats`` sessions that read ``expert_rows``
    distinct (layer, expert) pairs and whose live pages held
    ``layer_positions`` (layer, position) pairs in all (every position of a
    full layer, a window's worth of a sliding one): the fixed weights, the
    experts, the pages, and each seat's tails of a page read and written.
    Other writes (the logits) are left out."""
    tails = (2 * seats * conf["num_hidden_layers"] * page_tokens
             * layer_position_bytes(conf))
    return (fixed_weight_bytes(conf) + expert_rows * expert_bytes(conf)
            + layer_positions * layer_position_bytes(conf) + tails)


def page_bytes_counted(conf: dict, layer_positions: float,
                       expert_rows: float, page_tokens: int = 16) -> float:
    """One page program: the same weights once a page, the experts it
    counted, the (layer, position) pairs of the context it was handed
    (``kv.page_positions_read`` a page), one session's tails."""
    return step_bytes_counted(conf, layer_positions, expert_rows, 1,
                              page_tokens)


def decode_step_bytes(conf: dict, context_tokens: float) -> float:
    """The least one fused step must move: one seat whose token reads
    ``num_experts_per_tok`` experts a layer, the sliding layers over one
    window of the context at most."""
    pairs = (full_layers(conf) * context_tokens + window_layers(conf)
             * min(context_tokens, conf["sliding_window"]))
    return step_bytes_counted(
        conf, pairs, conf["num_hidden_layers"] * conf["num_experts_per_tok"],
        1)
