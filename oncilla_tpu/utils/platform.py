"""Platform plumbing on a stock JAX: virtual CPU devices for the examples
and the multichip dry run, and the persistent compile cache every entry
point that reaches an accelerator shares."""

from __future__ import annotations

import os

# The checkout root (the directory holding ``oncilla_tpu/``). The cache
# path is part of JAX's cache key, so it must be the same string in every
# process and every run — never a tempdir, a pid or a timestamp.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    reads it, so nothing is set in code; otherwise the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored). Call before the first
    compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe_devices() -> dict:
    """The device a result was measured on, as JAX reports it: every
    result line an entry point prints carries this."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def force_cpu_devices(n_devices: int) -> None:
    """Force the CPU platform with ``n_devices`` virtual devices. Call
    before first device use; if a backend is already up with too few
    devices it is dropped and re-initialized under the new config.

    Used by the multichip dry run and the examples; tests/conftest.py
    sets the equivalent env vars because it runs before jax is imported.
    """
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        # A backend is already initialized: keep it if it already
        # satisfies the request, else drop it and re-apply.
        devs = jax.devices()
        if devs[0].platform == "cpu" and len(devs) >= n_devices:
            return
        import jax.extend.backend

        jax.extend.backend.clear_backends()
        jax.config.update("jax_num_cpu_devices", n_devices)
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        raise RuntimeError(
            f"could not provision {n_devices} virtual CPU devices; "
            f"have {devs}"
        )
