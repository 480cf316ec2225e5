"""The window- and full-attention decoder family with softmax-routed experts,
all held, no gate and no shared expert (Mellum2): the adapter between a
configuration file that says ``"family": "swa_gqa_softmax_moe"`` and the
program's ``models/swa_moe.py``, the window family Laguna runs through too.

Published keys this family reads from the configuration file:
``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads`` (every layer's), ``num_key_value_heads``,
``head_dim``, ``layer_types`` and ``mlp_layer_types`` (the lists as
published, whole: a cut in depth reads their first ``num_hidden_layers``
entries), ``sliding_window``, ``num_experts`` (every one held here),
``num_experts_per_tok``, ``moe_intermediate_size``, ``rope_parameters``
(both groups; a group that states no ``partial_rotary_factor`` rotates the
whole head), ``max_position_embeddings``, ``rms_norm_eps`` and
``torch_dtype``. The family's own readings, which the published config
states by leaving keys out, are set here: no per-head gate, no shared
expert, no scaling of the routed weights, a softmax router.
``intermediate_size`` is read by nothing (no layer is dense). It holds the
keys of ``_HELD`` to the one value the program computes and raises on
anything else: on a ``gating`` key, a shared expert, a dense layer, a
score function other than softmax and a rotary type other than YaRN in the
full layers and plain in the window ones.

The program's config class is looked up here, at the top, through the
model package's public names: a program that lacks the family fails before
any device is touched.
"""

from __future__ import annotations

import dataclasses

from oncilla_tpu import models as program_models

SwaMoeConfig = program_models.SwaMoeConfig
# A window family without these switches would build Laguna's gate, shared
# expert and sigmoid router under this family's name: refuse it here.
if not {"gating", "scoring_func"} <= {
        f.name for f in dataclasses.fields(SwaMoeConfig)}:
    raise ImportError("the program's SwaMoeConfig has no gating and "
                      "scoring_func switches: it cannot run this family")

# references/<REFERENCE>.py: the plain float32 forward of this family.
REFERENCE = "swa_gqa_softmax_moe"
# bytes_models/<BYTES_MODEL>.py: the bytes its programs must move.
BYTES_MODEL = "swa_gqa_softmax_moe"
# The fused decode step and the page program as the profiler's
# ``XLA Modules`` line names them
# (``models/swa_moe.py::swa_decode_batch_step_jit``, ``swa_decode_page_jit``).
DECODE_STEP_PROGRAM = "swa_decode_batch_step"
PREFILL_PAGE_PROGRAM = "swa_decode_page"

# key: the one value the program computes (an absent key reads as it).
_HELD = {"gating": "none", "scoring_func": "softmax", "norm_topk_prob": True,
         "attention_bias": False, "tie_word_embeddings": False,
         "hidden_act": "silu", "shared_expert_intermediate_size": 0,
         "n_shared_experts": 0, "first_k_dense_replace": 0,
         "mlp_only_layers": [], "moe_routed_scaling_factor": 1,
         "routed_scaling_factor": 1, "max_window_layers": 0}
_ROPE_TYPES = {"full_attention": "yarn", "sliding_attention": "default"}


def program_config(conf: dict):
    """The configuration file's published keys as the program's config."""
    for key, want in _HELD.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{key} = {conf[key]!r}: the swa_gqa_softmax_moe "
                             f"family computes {want!r} only")
    kept = conf["num_hidden_layers"]
    kinds = set(conf["mlp_layer_types"][:kept])
    if kinds != {"sparse"}:
        raise ValueError(f"mlp_layer_types {sorted(kinds)}: every layer kept "
                         "here is sparse in this family")
    rope = {}
    for kind, want in _ROPE_TYPES.items():
        group = conf["rope_parameters"][kind]
        got = group.get("rope_type", "default")
        if got != want:
            raise ValueError(f"rope_parameters.{kind}.rope_type = {got!r}: "
                             f"the family computes {want!r}")
        rope[kind] = {"partial_rotary_factor": 1.0, **group}
    return SwaMoeConfig.from_published({
        **conf, "rope_parameters": rope,
        "num_attention_heads_per_layer": [conf["num_attention_heads"]] * kept,
        "router_experts": conf["num_experts"], "first_expert": 0,
        "shared_expert_intermediate_size": 0,
        "moe_routed_scaling_factor": 1.0, "gating": "none",
        "scoring_func": "softmax"})


def init_params(key, cfg):
    """The weights from the seed's key, traceable (the harness jits it: one
    call on the device, in the type they are served in)."""
    return cfg.init_params(key)
