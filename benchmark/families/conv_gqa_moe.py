"""The gated short-convolution / grouped-query-attention decoder family
with routed experts and no shared one (LFM2): the adapter between a
configuration file that says ``"family": "conv_gqa_moe"`` and the program's
``models/conv_moe.py``.

Published keys this family reads from the configuration file:
``vocab_size``, ``hidden_size``, ``intermediate_size``,
``moe_intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``layer_types`` (read to its
``num_hidden_layers``-th entry), ``num_dense_layers``, ``num_experts``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``conv_L_cache``,
``norm_eps``, ``rope_parameters.rope_theta``, ``max_position_embeddings``
and ``torch_dtype``; and ``head_dim``, which the source does not state
(``assumed`` in the file: ``hidden_size / num_attention_heads``). It holds
the keys of ``_HELD`` to the one value the program computes and raises on
anything else.

The program's config class is looked up here, at the top, through the
model package's public names: a program that lacks the family fails before
any device is touched.
"""

from __future__ import annotations

from oncilla_tpu import models as program_models

ConvMoeConfig = program_models.ConvMoeConfig

# references/<REFERENCE>.py: the plain float32 forward of this family.
REFERENCE = "conv_gqa_moe"
# bytes_models/<BYTES_MODEL>.py: the bytes its programs must move.
BYTES_MODEL = "conv_gqa_moe"
# The fused decode step and the page program as the profiler's
# ``XLA Modules`` line names them
# (``models/conv_moe.py::conv_decode_batch_step_jit``,
# ``conv_decode_page_jit``).
DECODE_STEP_PROGRAM = "conv_decode_batch_step"
PREFILL_PAGE_PROGRAM = "conv_decode_page"

_HELD = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
         "tie_word_embeddings": True}


def program_config(conf: dict):
    """The configuration file's published keys as the program's config."""
    for key, want in _HELD.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{key} = {conf[key]!r}: the conv_gqa_moe "
                             f"family computes {want!r} only")
    got = conf["rope_parameters"].get("rope_type", "default")
    if got != "default":
        raise ValueError(f"rope_parameters.rope_type = {got!r}: the family "
                         "computes plain rotary only")
    return ConvMoeConfig.from_published(conf)


def init_params(key, cfg):
    """The weights from the seed's key, traceable (the harness jits it: one
    call on the device, in the type they are served in)."""
    return cfg.init_params(key)
