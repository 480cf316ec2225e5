"""The dense grouped-query decoder family (InternLM2, Mistral): the adapter
between a configuration file that says ``"family": "dense_gqa"`` and the
program's ``models/llama.py``. A family's adapter is the only place where
the benchmark imports a model module of ``oncilla_tpu``; ``harness.py``
finds it through the configuration's ``family`` key and takes from it
everything that depends on the architecture.

Published keys this family reads from the configuration file:
``vocab_size``, ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``max_position_embeddings``, ``rope_theta``, ``rms_norm_eps``,
``torch_dtype`` and ``sliding_window``.
"""

from __future__ import annotations

# references/<REFERENCE>.py: the plain float32 forward of this family.
REFERENCE = "dense_gqa"
# bytes_models/<BYTES_MODEL>.py: the bytes a fused step of it must move.
BYTES_MODEL = "dense_gqa"
# The fused decode step's program as the profiler's ``XLA Modules`` line
# names it (``models/kv_paging.py::paged_decode_batch_step_jit``).
DECODE_STEP_PROGRAM = "paged_decode_batch_step"


def program_config(conf: dict):
    """The configuration file's published keys as the program's config."""
    from oncilla_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab=conf["vocab_size"], dim=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        ffn_hidden=conf["intermediate_size"],
        max_seq=conf["max_position_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        dtype=conf["torch_dtype"], window=conf.get("sliding_window"),
    )


def init_params(key, cfg):
    """The weights from the seed's key, traceable (the harness jits it: one
    call on the device, in the type they are served in)."""
    from oncilla_tpu.models import llama

    return llama.init_params(key, cfg)
