"""Runtime feature detection (REF:src/libinfo.cc, REF:python/mxnet/runtime.py).

The reference exposes its build-time feature matrix (CUDA? CUDNN? MKLDNN?
DIST_KVSTORE? ...) via ``mx.runtime.feature_list()``.  Here features are
determined live from the JAX installation: backend platforms, device counts,
and which optional subsystems of this framework are importable.
"""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list", "fetch_sync"]


def fetch_sync(x):
    """Synchronize on device work by FETCHING data to the host, returning
    the fetched numpy array.

    ``jax.block_until_ready`` is the execution barrier on the installed
    backend.  A device->host fetch of a value that depends on the work
    is an equally valid one, and it is the one to use when the host
    wants the value anyway: programs on one device execute in submission
    order, so fetching the LAST result proves all prior work completed.
    Pass a small slice/scalar (e.g. ``loss`` or ``out[:1]``), not a big
    tensor — the copy is inside the timed region.  Used by
    tools/bandwidth.py; benchmark/run.py and tools/tpu_validate.py fetch
    their loss scalars inline."""
    import numpy as np
    return np.asarray(x)


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return "[%s: %s]" % ("✔" if self.enabled else "✖", self.name)


def _probe():
    feats = {"JAX": False, "TPU": False, "GPU": False, "CPU": True,
             "PALLAS": False, "X64": False, "DIST_KVSTORE": False}
    try:
        import jax
        feats["JAX"] = True
    except Exception:
        jax = None
    if jax is not None:
        try:
            platform = jax.default_backend()
            feats["TPU"] = platform == "tpu"
            feats["GPU"] = platform in ("gpu", "cuda", "rocm")
        except Exception:
            pass
        try:
            feats["PALLAS"] = bool(__import__("jax.experimental.pallas",
                                              fromlist=["pallas"]))
        except Exception:
            pass
        try:
            feats["X64"] = bool(jax.config.read("jax_enable_x64"))
        except Exception:
            pass
        try:
            import jax.distributed  # noqa: F401
            feats["DIST_KVSTORE"] = True
        except Exception:
            pass
    for mod, name in [("cv2", "OPENCV"),
                      ("PIL", "PIL"),            # image decode path
                      ("orbax.checkpoint", "ORBAX")]:
        try:
            __import__(mod)
            feats[name] = True
        except Exception:
            feats[name] = False
    # native C++ components of this framework (RecordIO fast path)
    try:
        from .lib import recordio_cpp  # noqa: F401
        feats["CPP_RECORDIO"] = True
    except Exception:
        feats["CPP_RECORDIO"] = False
    feats["BF16"] = feats["JAX"]
    feats["INT8_QUANTIZATION"] = True
    feats["PROFILER"] = True
    return feats


class Features(dict):
    """dict of name -> Feature, like the reference's LibInfo wrapper."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _probe().items()})

    def is_enabled(self, name):
        feat = self.get(name.upper())
        return bool(feat and feat.enabled)

    def __repr__(self):
        return "[%s]" % ", ".join(map(str, self.values()))


def feature_list():
    """Check the library for compile-time/runtime features it supports."""
    return list(Features().values())


def set_compilation_cache(directory, min_compile_time_secs=1.0):
    """Enable XLA's persistent compilation cache (REF analog: the
    reference's CachedOp graphs lived in-process only; on TPU the first
    compile of a big train step costs minutes, and this cache carries it
    across PROCESSES/restarts — essential for the die-and-restart
    elastic contract in tpu_mx.elastic).

    directory: cache dir (created if missing).  Programs whose compile
    took less than min_compile_time_secs are not cached (they would only
    add disk churn)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(directory))
    _set_cache_thresholds(min_compile_time_secs)


def _set_cache_thresholds(min_compile_time_secs=1.0):
    import jax
    from jax._src import compilation_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches the cache-used decision at the FIRST compile of the
    # process (compilation_cache._cache_checked); if anything compiled
    # before this call — an earlier train step, another test — the
    # directory would be silently ignored forever.  reset_cache()
    # unlatches so the next compile re-evaluates with it configured.
    compilation_cache.reset_cache()


def enable_shared_compilation_cache():
    """The one place the program chooses a compile-cache directory;
    chip_smoke.py, benchmark/run.py and tools/tpu_validate.py call it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is the
    environment's: jax reads the variable itself, and this function sets
    no directory in code, only the thresholds.  Otherwise the cache goes
    to the fixed ``<checkout>/.jax_cache`` — never a temporary, pid- or
    time-derived path, which a later process could not find again.
    BENCH_COMPILE_CACHE=0 disables it (e.g. when the directory is
    corrupted/unwritable).  Returns the directory in use, or None when
    disabled."""
    import os
    if os.environ.get("BENCH_COMPILE_CACHE", "1") != "1":
        return None
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if directory:
        _set_cache_thresholds()
    else:
        directory = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        set_compilation_cache(directory)
    return directory


def clear_compilation_cache():
    """Drop the in-memory jit cache (the persistent dir is untouched)."""
    import jax
    jax.clear_caches()
