"""Model-side public surface: the flagship llama family + KV paging.

``serving/`` (and any other runtime consumer) imports the model API
through this package rather than reaching into submodules::

    from oncilla_tpu.models import (
        LlamaConfig, PagedKVCache, BucketedPagedDecoder,
        paged_decode_step_jit,
    )

Attribute access is lazy (PEP 562) so importing a sibling that only
needs one symbol does not eagerly build every model module; submodules
(``models.llama``, ``models.kv_paging``, ...) stay importable directly.
"""

from __future__ import annotations

_EXPORTS = {
    # llama: config + builders + the decode/generate entry points.
    "LlamaConfig": "llama",
    "init_params": "llama",
    "init_params_host": "llama",
    "forward": "llama",
    "loss_fn": "llama",
    "decode_step": "llama",
    "decode_loop": "llama",
    "make_kv_cache": "llama",
    "sample_token": "llama",
    "generate": "llama",
    # kv_paging: the OCM-paged decode family.
    "PagedKVCache": "kv_paging",
    "PagedDecoder": "kv_paging",
    "BucketedPagedDecoder": "kv_paging",
    "paged_decode_step": "kv_paging",
    "paged_decode_step_jit": "kv_paging",
    "paged_decode_batch_step_jit": "kv_paging",
    "paged_pool_write_row_jit": "kv_paging",
    "paged_decode_page_jit": "kv_paging",
    "paged_generate_page_jit": "kv_paging",
    "PagedFamily": "kv_paging",
    "PageKind": "kv_paging",
    # latent_moe: latent attention, routed experts, hyper-connections.
    "LatentMoeConfig": "latent_moe",
    # kda_latent: delta-rule linear attention beside latent attention,
    # group-limited experts of which the chip holds a share.
    "KdaLatentConfig": "kda_latent",
    # swa_moe: window and full attention layers with per-head gates, a
    # page of two kinds, experts of which the chip holds a share.
    "SwaMoeConfig": "swa_moe",
    # conv_moe: gated short convolutions beside normed grouped-query
    # attention, experts without a shared one, a carry of two rows a layer.
    "ConvMoeConfig": "conv_moe",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)
