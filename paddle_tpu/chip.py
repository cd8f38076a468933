"""What an entry point does before it compiles for the chip.

Three things every program that runs on the TPU needs and that
`import paddle_tpu` must NOT do on its own (tests and data workers import
the package too):

  - enable_compile_cache(): one persistent XLA compile cache, placeable
    from outside through JAX_COMPILATION_CACHE_DIR;
  - require_tpu(): fail — not skip, not fall back — when jax's default
    backend is not a TPU, and say which device answered;
  - chip_env(i): the environment that binds a CHILD process to one local
    chip. A chip belongs to one process at a time, so a parent that
    starts N children keeps off jax itself and hands each child its chip
    before the child imports jax.
"""
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache():
    """Turn on jax's persistent compilation cache.

    If JAX_COMPILATION_CACHE_DIR is set, jax already reads it and this
    sets nothing. Otherwise the cache lives at <checkout>/.jax_cache — a
    fixed path resolved from this file (the directory is part of the
    cache key, so it must not move between runs) — and the variable is
    exported so child processes share it. Call before the first compile.
    Returns the directory this call chose, or None when the environment
    chose."""
    if os.environ.get(CACHE_ENV):
        return None
    path = os.path.join(REPO_ROOT, ".jax_cache")
    os.environ[CACHE_ENV] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_stamp():
    """The device as jax reports it plus the installed versions — what
    every on-chip result is stamped with."""
    from importlib import metadata
    import jax
    import jaxlib
    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def require_tpu():
    """device_stamp(), or RuntimeError when the default backend is not a
    TPU: a measurement path that finds no chip fails."""
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: jax reports platform={stamp['platform']!r} "
            f"device_kind={stamp['kind']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); this path runs on the "
            "chip only")
    return stamp


def chip_env(index):
    """Environment variables that make libtpu show a process exactly one
    local chip (`index`) as its own one-chip topology. Set them in the
    child's environment BEFORE it imports jax; without them the first
    child to start takes every chip on the host and the rest fail."""
    return {"TPU_VISIBLE_CHIPS": str(int(index)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}
