"""Process-wide JAX configuration for the engine.

Import this module before tracing any engine-adjacent jitted function:
* ``jax_enable_x64`` — event timestamps are int64 (epoch-ms exceeds int32);
  partial aggregates remain explicit float32.
* persistent compilation cache — kernels are static per window/agg mix, so
  repeat runs (tests, benchmarks) skip XLA compilation entirely. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  here overrides it; otherwise the cache sits at one fixed path inside
  the checkout, so a second run, or a copy of the checkout, finds what
  an earlier run compiled.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
