"""repro — distributed tensor query processing + multi-pod LM framework in JAX.

Reproduction of "Terabyte-Scale Analytics in the Blink of an Eye" (distributed
TQP on collective communication) adapted to TPU pods, plus the assigned
LM-architecture zoo, training/serving substrate, and multi-pod launch tooling.

x64 is enabled globally: SQL analytics needs real int64 keys (TPC-H SF>=1000
orderkeys exceed int32).  All model code specifies dtypes explicitly, so LM
paths remain bf16/f32/int32.
"""
import os

import jax

jax.config.update("jax_enable_x64", True)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this (never the package import).  A directory given by
    ``JAX_COMPILATION_CACHE_DIR`` is left as JAX found it.  Otherwise the
    cache lives at the fixed ``<checkout>/.jax_cache``, so a later run from
    the same checkout finds what an earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__version__ = "1.0.0"
