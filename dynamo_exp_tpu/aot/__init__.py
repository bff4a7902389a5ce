"""AOT variant precompilation and warm-boot provisioning (docs/aot.md).

A cold engine pays the whole compiled-variant lattice in first-traffic
compiles — PR 8's compile attribution showed that delay dominating
scale-up, and the planner models it as ``SloTargets.provision_s``. This
package makes the lattice a *build artifact* instead of a first-traffic
tax:

- :mod:`.lattice` enumerates the full compile lattice offline from an
  :class:`~dynamo_exp_tpu.engine.EngineConfig` as a deterministic,
  hashable :class:`CompileManifest` — sharing the variant-key function
  (:func:`resolve_ragged_key`) with the engine's ``_ragged_fn``, so the
  manifest can never drift from what the loop actually dispatches.
- :mod:`.compile` AOT-lowers and compiles every manifest entry
  (``.lower().compile()`` with the engine's explicit shardings) and
  wires the JAX persistent compilation cache, so a second process loads
  serialized executables instead of recompiling.
- :mod:`.warmup` is ``TPUEngine.prewarm``'s implementation: populate
  the engine's ``_ragged_fns`` (and the gather/scatter/COW kernels)
  from the cache *before* the engine accepts traffic, and seed the
  dispatch profiler's freshness state so steady-state compile-miss
  flatness holds from the very first dispatch.

Operator surface: ``llmctl aot compile|list|warm|smoke`` and
``dynamo_exp_tpu.run --prewarm``. The cache directory is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``--compile-cache-dir``,
else ``<checkout>/.jax_cache`` (:func:`resolve_cache_dir`).
"""

from .compile import (
    aot_compile,
    enable_persistent_cache,
    fresh_cache_dir,
    manifest_for_engine,
    resolve_cache_dir,
)
from .lattice import (
    CompileManifest,
    RaggedVariant,
    build_manifest,
    mixed_token_buckets,
    page_bound_buckets,
    page_move_buckets,
    ragged_variants,
    resolve_ragged_key,
    windowed_token_buckets,
)
from .warmup import PrewarmReport, prewarm_engine

__all__ = [
    "CompileManifest",
    "PrewarmReport",
    "RaggedVariant",
    "aot_compile",
    "build_manifest",
    "enable_persistent_cache",
    "fresh_cache_dir",
    "manifest_for_engine",
    "mixed_token_buckets",
    "page_bound_buckets",
    "page_move_buckets",
    "prewarm_engine",
    "ragged_variants",
    "resolve_cache_dir",
    "resolve_ragged_key",
    "windowed_token_buckets",
]
