"""ref: python/paddle/utils/cpp_extension/ — custom C++ op builds.

TPU-native shape: custom ops are ctypes-loaded C ABI libraries (the
csrc/ convention: tcp_store.cc, ps_service.cc build via g++ on first
import) or Pallas kernels; the reference's CUDAExtension tier does not
apply. load() compiles a .cc into a shared library and returns the
ctypes handle."""
import hashlib
import os
import subprocess

__all__ = ["load", "get_build_directory"]


def build_if_stale(src, out, opt="-O3"):
    """Compile `src` into the shared library `out` unless `out` was
    built from exactly this source with these flags. Keyed on a content
    hash kept beside the library, not on mtimes: a copy of the tree (the
    chip tool's, a fresh checkout beside a leftover .so) does not keep
    them. Builds to a temporary name and renames, so processes that
    start together never load a half-written library."""
    cmd = ["g++", opt, "-std=c++17", "-shared", "-fPIC"]
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + " ".join(cmd).encode()).hexdigest()
    stamp = out + ".srchash"
    try:
        with open(stamp) as f:
            fresh = os.path.exists(out) and f.read().strip() == digest
    except FileNotFoundError:
        fresh = False
    if not fresh:
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(cmd + ["-o", tmp, src, "-lpthread"], check=True,
                       capture_output=True)
        os.replace(tmp, out)
        with open(tmp, "w") as f:
            f.write(digest)
        os.replace(tmp, stamp)
    return out


def get_build_directory():
    d = os.environ.get("PADDLE_EXTENSION_DIR",
                       os.path.join(os.path.expanduser("~"), ".cache",
                                    "paddle_extensions"))
    os.makedirs(d, exist_ok=True)
    return d


def load(name, sources, extra_cxx_cflags=None, extra_include_paths=None,
         build_directory=None, verbose=False, **kw):
    """Build `sources` (C++ only) into lib<name>.so and ctypes-load it —
    the same pipeline paddle_tpu's own csrc/ uses."""
    import ctypes
    bdir = build_directory or get_build_directory()
    out = os.path.join(bdir, f"lib{name}.so")
    srcs = [str(s) for s in sources]
    newest = max((os.path.getmtime(s) for s in srcs), default=0.0)
    if not os.path.exists(out) or os.path.getmtime(out) < newest:
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", out]
        for inc in (extra_include_paths or []):
            cmd += ["-I", str(inc)]
        cmd += (extra_cxx_cflags or []) + srcs + ["-lpthread"]
        if verbose:
            print("cpp_extension:", " ".join(cmd))
        subprocess.run(cmd, check=True)
    return ctypes.CDLL(out)


def CppExtension(sources, *args, **kwargs):
    """ref: cpp_extension.py CppExtension — a setuptools.Extension
    configured for paddle C++ ops; here a config dict consumed by
    setup()/load() (the csrc g++ pipeline)."""
    return {"sources": [str(s) for s in sources],
            "include_dirs": kwargs.get("include_dirs", []),
            "extra_compile_args": kwargs.get("extra_compile_args", []),
            "kind": "cpp"}


def CUDAExtension(*args, **kwargs):
    raise RuntimeError(
        "CUDAExtension is not available in a TPU/XLA build; write TPU "
        "kernels in Pallas (paddle_tpu/ops/pallas) and host-side native "
        "code as CppExtension")


def setup(name=None, ext_modules=None, **kwargs):
    """ref: cpp_extension.py setup — build the extensions in place via
    the same g++ pipeline as load(); returns the built library handles."""
    exts = ext_modules if isinstance(ext_modules, (list, tuple)) \
        else [ext_modules]
    handles = []
    for i, ext in enumerate(exts):
        if ext is None:
            continue
        if not isinstance(ext, dict) or ext.get("kind") != "cpp":
            raise TypeError("setup takes CppExtension(...) modules")
        handles.append(load(f"{name or 'paddle_ext'}_{i}", ext["sources"],
                            extra_cxx_cflags=ext["extra_compile_args"],
                            extra_include_paths=ext["include_dirs"]))
    return handles
