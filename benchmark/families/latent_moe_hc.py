"""The latent-attention / routed-expert / hyper-connection decoder family
(Xing4.0, the DeepSeek-V3 shape with manifold-constrained hyper-connections):
the adapter between a configuration file that says
``"family": "latent_moe_hc"`` and the program's ``models/latent_moe.py``.

Published keys this family reads from the configuration file:
``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size``, ``first_k_dense_replace``, ``moe_intermediate_size``,
``n_routed_experts``, ``n_shared_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``, ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``,
``max_position_embeddings``, ``rope_theta``, the ``rope_scaling`` group,
``rms_norm_eps`` and ``torch_dtype``. It holds ``scoring_func`` to
``sigmoid``, ``n_group`` and ``topk_group`` to 1 (no group limit),
``norm_topk_prob`` to true and ``num_nextn_predict_layers`` to 0 (greedy
decoding reads no draft head), because the program computes nothing else.

The program's config class is looked up here, at the top, through the
model package's public names: a program that lacks the family fails before
any device is touched.
"""

from __future__ import annotations

from oncilla_tpu import models as program_models

LatentMoeConfig = program_models.LatentMoeConfig

# references/<REFERENCE>.py: the plain float32 forward of this family.
REFERENCE = "latent_moe_hc"
# bytes_models/<BYTES_MODEL>.py: the bytes its programs must move.
BYTES_MODEL = "latent_moe_hc"
# The fused decode step and the page program as the profiler's
# ``XLA Modules`` line names them
# (``models/latent_moe.py::latent_decode_batch_step_jit``,
# ``latent_decode_page_jit``).
DECODE_STEP_PROGRAM = "latent_decode_batch_step"
PREFILL_PAGE_PROGRAM = "latent_decode_page"

_HELD = {"scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "num_nextn_predict_layers": 0,
         "moe_layer_freq": 1}


def program_config(conf: dict):
    """The configuration file's published keys as the program's config."""
    for key, want in _HELD.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{key} = {conf[key]!r}: the latent_moe_hc "
                             f"family computes {want!r} only")
    return LatentMoeConfig.from_published(conf)


def init_params(key, cfg):
    """The weights from the seed's key, traceable (the harness jits it: one
    call on the device, in the type they are served in)."""
    return cfg.init_params(key)
