"""AOT compilation of the lattice + persistent-cache wiring
(docs/aot.md "Compiling the lattice offline").

``aot_compile`` walks a :class:`~.lattice.CompileManifest` and runs the
SNIPPETS-grounded ahead-of-time compile step for every entry:
``jit_fn.lower(*args).compile()`` with the engine's explicit shardings
(params / KV pools are the engine's real committed arrays — ``lower``
reads their avals and shardings without executing or consuming donated
buffers). With the JAX persistent compilation cache enabled, every
compiled executable serializes to ``cache_dir`` keyed by its HLO hash —
so a *different process* (a freshly provisioned instance) that builds
the same programs deserializes them instead of recompiling, which is
the entire warm-boot story.

The lowering arguments come from :func:`~.warmup.variant_call_args` —
the same tuples ``prewarm_engine`` executes with — so the compiler, the
warmer, and the live dispatch sites cannot drift apart without the
prewarm-smoke gate catching it.
"""

from __future__ import annotations

import logging
import os
import shutil
import time
from dataclasses import dataclass, field

from .lattice import CompileManifest, build_manifest

log = logging.getLogger(__name__)

# JAX's own variable: set from outside (the chip tool, a deployment), it
# IS the cache directory and this program never points JAX elsewhere.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Unset, the cache lives at one fixed path in the checkout: the path is
# part of what makes a later run find an earlier run's executables, so
# it must never be derived from a pid, a clock or a temp name.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
MANIFEST_FILENAME = "manifest.json"


def _placed_from_outside() -> str:
    return os.environ.get(CACHE_ENV, "").strip()


def resolve_cache_dir(requested: str = "") -> str:
    """THE compile-cache directory of this process: ``$JAX_COMPILATION_
    CACHE_DIR`` when set (``requested`` is then ignored), else
    ``requested`` (a ``--compile-cache-dir`` flag), else the fixed
    in-checkout default."""
    return _placed_from_outside() or requested or DEFAULT_CACHE_DIR


def enable_persistent_cache(requested: str = "") -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`resolve_cache_dir` (created if missing) and drop the
    min-compile-time / min-entry-size gates so even small variants
    serialize. Returns the directory in use."""
    import jax
    from jax._src.compilation_cache import reset_cache

    cache_dir = resolve_cache_dir(requested)
    os.makedirs(cache_dir, exist_ok=True)
    if not _placed_from_outside():
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The cache object is memoized at first use: a process that already
    # compiled anything (engine construction jits device_puts) latched
    # its state and would ignore the options above — reset so the
    # updated config is actually read.
    reset_cache()
    return cache_dir


def fresh_cache_dir(name: str) -> str:
    """An EMPTY cache for a cold-vs-warm comparison: the fixed
    sub-directory ``<default>/<name>``, emptied now. Cold arms need a
    cache with nothing in it, not one that moves. Refused while
    ``$JAX_COMPILATION_CACHE_DIR`` pins the process's cache — that
    directory is shared and is neither emptied nor left."""
    if _placed_from_outside():
        raise SystemExit(
            f"{name}: a cold arm needs an empty compile cache, and "
            f"${CACHE_ENV} pins this process to a shared one; unset it"
        )
    path = os.path.join(DEFAULT_CACHE_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    return enable_persistent_cache(path)


def manifest_for_engine(engine, **kwargs) -> CompileManifest:
    """The full compile lattice for a live engine: its resolved
    attention implementation, its mesh shape, this process's jax."""
    import jax

    return build_manifest(
        engine.cfg,
        attn_impl=engine._attn_impl,
        mesh_shape=dict(engine.mesh.shape),
        jax_version=jax.__version__,
        interpret=engine._attn_interpret,
        **kwargs,
    )


@dataclass
class AotCompileReport:
    manifest_hash: str = ""
    compiled: int = 0
    seconds: float = 0.0
    cache_dir: str = ""
    failed: list[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "manifest_hash": self.manifest_hash,
            "compiled": self.compiled,
            "seconds": round(self.seconds, 3),
            "cache_dir": self.cache_dir,
            "failed": list(self.failed),
        }


def aot_compile(
    engine,
    manifest: CompileManifest | None = None,
    cache_dir: str = "",
) -> AotCompileReport:
    """AOT-lower and compile every manifest entry through the engine's
    own program builders. Pure compilation: nothing executes, no
    donated buffer is consumed, the engine's ``_ragged_fns`` cache ends
    up populated with the (still-unexecuted) jit wrappers. Every
    executable is also serialized to the persistent cache
    (:func:`resolve_cache_dir`; ``cache_dir`` is the requested path)
    for other processes; the manifest JSON is dropped next to the cache
    entries for ``llmctl aot list`` and hash checks."""
    import jax.numpy as jnp

    from .warmup import variant_call_args

    cache_dir = enable_persistent_cache(cache_dir)
    if manifest is None:
        manifest = manifest_for_engine(engine)
    t0 = time.monotonic()  # dynlint: determinism(prewarm wall-clock metric)
    report = AotCompileReport(
        manifest_hash=manifest.hash(), cache_dir=cache_dir
    )
    for variant in manifest.ragged:
        fn = engine._ragged_fn_from_key(variant.key)
        try:
            fn.lower(*variant_call_args(engine, variant.key)).compile()
            report.compiled += 1
        except Exception as e:  # noqa: BLE001 - record, keep compiling
            log.exception("AOT compile failed for %s", variant)
            report.failed.append(f"{variant.key}: {e}")
    k, v = engine.k_cache, engine.v_cache
    for bucket in manifest.move_buckets:
        pids = jnp.zeros(bucket, jnp.int32)
        try:
            engine._gather_pages.lower(k, v, pids).compile()
            # The scatter's page payloads have the gather's output
            # shape: [L, bucket, page_size, HkvD] in the KV dtype.
            # (a pool's own width: a latent model's second has no lanes)
            k_page, v_page = (
                jnp.zeros(
                    (pool.shape[0], bucket, *pool.shape[2:]),
                    engine.cfg.kv_dtype_jnp,
                )
                for pool in (k, v)
            )
            engine._inject_pages.lower(k, v, pids, k_page, v_page).compile()
            report.compiled += 2
        except Exception as e:  # noqa: BLE001
            log.exception("AOT compile failed for move bucket %d", bucket)
            report.failed.append(f"move:{bucket}: {e}")
    try:
        zero = jnp.asarray(0, jnp.int32)
        engine._cow_pages.lower(k, v, zero, zero).compile()
        engine._init_row.lower(
            engine._counts, engine.cfg.max_decode_slots, 0
        ).compile()
        report.compiled += 2
    except Exception as e:  # noqa: BLE001
        log.exception("AOT compile failed for cow/init_row")
        report.failed.append(f"cow/init_row: {e}")
    report.seconds = time.monotonic() - t0  # dynlint: determinism(prewarm wall-clock metric)
    write_manifest(cache_dir, manifest)
    log.info(
        "aot: compiled %d variants in %.2fs (manifest %s)%s",
        report.compiled, report.seconds, report.manifest_hash[:12],
        f", {len(report.failed)} FAILED" if report.failed else "",
    )
    return report


def write_manifest(cache_dir: str, manifest: CompileManifest) -> str:
    path = os.path.join(cache_dir, MANIFEST_FILENAME)
    with open(path, "w", encoding="utf-8") as f:
        f.write(manifest.to_json(indent=2))
        f.write("\n")
    return path


def read_manifest(cache_dir: str) -> CompileManifest | None:
    path = os.path.join(cache_dir, MANIFEST_FILENAME)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return CompileManifest.from_json(f.read())
