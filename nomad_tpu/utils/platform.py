"""Device-platform identification and process-wide JAX set-up shared by
fingerprints, kernels and the entry points."""
import os

# Fixed, derived from the package path: the directory is part of every
# cache key's lookup, so a cache that moves between processes never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_cache_configured = False


def is_tpu_platform(platform: str) -> bool:
    """Whether a jax device platform string is a TPU."""
    return platform == "tpu"


def ensure_compile_cache() -> None:
    """Place JAX's persistent compilation cache for this process.  The
    scheduling programs cost tens of seconds of XLA compile per shape
    bucket; the cache makes that a once-per-checkout tax.

    Must run before the first compilation in the process — JAX latches
    "cache unused" on the first compile that finds no directory — so
    the server calls it at construction, not at the first batch.  Does
    NOT initialize a backend.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set the deployment owns the location and nothing is set in code;
    otherwise the cache is ``<checkout>/.jax_cache``.  A process pinned
    to the CPU backend (``JAX_PLATFORMS=cpu``) sets none: its compiles
    take a second, and XLA:CPU warns of SIGILL on every cached
    executable it loads.  Disable with NOMAD_TPU_NO_COMPILE_CACHE=1."""
    global _cache_configured
    if _cache_configured:
        return
    _cache_configured = True
    from . import knobs

    if knobs.get_bool("NOMAD_TPU_NO_COMPILE_CACHE"):
        return
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    if jax.config.jax_platforms == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def virtual_mesh_env(n_devices: int, base_env=None) -> dict:
    """Subprocess environment that provisions an ``n_devices`` virtual
    CPU mesh: any pre-existing forced-device-count flag is stripped
    from XLA_FLAGS (it may be lower than needed), exactly ``n_devices``
    is pinned, and the platform is forced to CPU — so the child never
    opens an accelerator its parent may hold.  XLA reads the flag at
    backend init, so this only works for a FRESH interpreter — the one
    shared recipe behind the selfcheck mesh drill, bench config_mesh,
    and the driver dryrun (tests/conftest.py inlines a variant because
    it must run before any import).
    """
    import re

    env = dict(base_env if base_env is not None else os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags.strip() +
                        f" --xla_force_host_platform_device_count="
                        f"{n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env
