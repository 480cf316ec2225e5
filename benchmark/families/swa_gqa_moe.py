"""The window- and full-attention decoder family with per-head gates and
routed experts of which the chip holds a share (Laguna): the adapter
between a configuration file that says ``"family": "swa_gqa_moe"`` and the
program's ``models/swa_moe.py``.

Published keys this family reads from the configuration file:
``vocab_size`` (the slice held here), ``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_key_value_heads``,
``head_dim``, ``layer_types``, ``num_attention_heads_per_layer`` and
``mlp_layer_types`` (the lists as published, whole: a cut in depth reads
their first ``num_hidden_layers`` entries), ``sliding_window``,
``num_experts`` (the experts HELD here), ``num_experts_per_tok``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``moe_routed_scaling_factor``, ``rope_parameters`` (both groups),
``max_position_embeddings``, ``rms_norm_eps`` and ``torch_dtype``; and two
keys of the cut: ``router_experts`` (the router's width: every expert of
the deployment) and ``first_expert`` (where the held range starts). It holds
the keys of ``_HELD`` to the one value the program computes and raises on
anything else.

The program's config class is looked up here, at the top, through the
model package's public names: a program that lacks the family fails before
any device is touched.
"""

from __future__ import annotations

from oncilla_tpu import models as program_models

SwaMoeConfig = program_models.SwaMoeConfig

# references/<REFERENCE>.py: the plain float32 forward of this family.
REFERENCE = "swa_gqa_moe"
# bytes_models/<BYTES_MODEL>.py: the bytes its programs must move.
BYTES_MODEL = "swa_gqa_moe"
# The fused decode step and the page program as the profiler's
# ``XLA Modules`` line names them
# (``models/swa_moe.py::swa_decode_batch_step_jit``, ``swa_decode_page_jit``).
DECODE_STEP_PROGRAM = "swa_decode_batch_step"
PREFILL_PAGE_PROGRAM = "swa_decode_page"

_HELD = {"gating": "per-head", "norm_topk_prob": True,
         "attention_bias": False, "decoder_sparse_step": 1,
         "mlp_only_layers": [0], "moe_apply_router_weight_on_input": False,
         "moe_router_logit_softcapping": 0, "tie_word_embeddings": False}
_ROPE_TYPES = {"full_attention": "yarn", "sliding_attention": "default"}


def program_config(conf: dict):
    """The configuration file's published keys as the program's config."""
    for key, want in _HELD.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{key} = {conf[key]!r}: the swa_gqa_moe family "
                             f"computes {want!r} only")
    kept = conf["num_hidden_layers"]
    gates = conf.get("gating_types", ["per_head"] * kept)[:kept]
    if set(gates) != {"per_head"}:
        raise ValueError(f"gating_types {sorted(set(gates))}: a layer kept "
                         "here gates otherwise than a head at a time")
    for kind, want in _ROPE_TYPES.items():
        got = conf["rope_parameters"][kind].get("rope_type", "default")
        if got != want:
            raise ValueError(f"rope_parameters.{kind}.rope_type = {got!r}: "
                             f"the family computes {want!r}")
    return SwaMoeConfig.from_published(conf)


def init_params(key, cfg):
    """The weights from the seed's key, traceable (the harness jits it: one
    call on the device, in the type they are served in)."""
    return cfg.init_params(key)
