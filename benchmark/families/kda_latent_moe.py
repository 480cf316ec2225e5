"""The delta-rule / latent-attention decoder family with group-limited
routed experts of which the chip holds a share (Ling-3.0-flash): the
adapter between a configuration file that says
``"family": "kda_latent_moe"`` and the program's ``models/kda_latent.py``.

Published keys this family reads from the configuration file:
``vocab_size`` (the slice held here), ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``head_dim``,
``q_lora_rank`` (null), ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``intermediate_size``,
``first_k_dense_replace``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``num_experts`` (the experts HELD
here), ``num_experts_per_tok``, ``n_group``, ``topk_group``,
``routed_scaling_factor``, ``layer_group_size``,
``short_conv_kernel_size``, ``kda_lower_bound``,
``max_position_embeddings``, ``rope_theta``, ``rms_norm_eps`` and
``torch_dtype``; and two keys of the cut: ``router_experts`` (the router's
width: every expert of the deployment) and ``first_expert`` (where the held
range starts). It holds the keys of ``_HELD`` to the one value the program
computes and raises on anything else.

The program's config class is looked up here, at the top, through the
model package's public names: a program that lacks the family fails before
any device is touched.
"""

from __future__ import annotations

from oncilla_tpu import models as program_models

KdaLatentConfig = program_models.KdaLatentConfig

# references/<REFERENCE>.py: the plain float32 forward of this family.
REFERENCE = "kda_latent_moe"
# bytes_models/<BYTES_MODEL>.py: the bytes its programs must move.
BYTES_MODEL = "kda_latent_moe"
# The fused decode step and the page program as the profiler's
# ``XLA Modules`` line names them
# (``models/kda_latent.py::kda_decode_batch_step_jit``,
# ``kda_decode_page_jit``).
DECODE_STEP_PROGRAM = "kda_decode_batch_step"
PREFILL_PAGE_PROGRAM = "kda_decode_page"

_HELD = {"score_function": "sigmoid", "n_group": 8, "topk_group": 4,
         "layer_group_size": 6, "norm_topk_prob": True,
         "moe_router_enable_expert_bias": True, "q_lora_rank": None,
         "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
         "linear_silu": True, "use_qk_norm": True,
         "gated_attention_proj_granularity_type": "head_wise",
         "num_kv_heads_for_linear_attn": 0, "use_mla_nope": False,
         "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False, "use_nGPT": False}


def program_config(conf: dict):
    """The configuration file's published keys as the program's config."""
    for key, want in _HELD.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{key} = {conf[key]!r}: the kda_latent_moe "
                             f"family computes {want!r} only")
    layers = range(conf["num_hidden_layers"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(conf.get(key, [0] * len(layers))[i] for i in layers):
            raise ValueError(f"{key}: a layer kept here clamps its SwiGLU; "
                             "the family computes none")
    return KdaLatentConfig.from_published(conf)


def init_params(key, cfg):
    """The weights from the seed's key, traceable (the harness jits it: one
    call on the device, in the type they are served in)."""
    return cfg.init_params(key)
