"""Profiling hooks: jax.profiler server + a traced block.

Reference capability: SURVEY.md §5 tracing — the reference has JSONL
tracing but no accelerator profiler; the TPU-native extension is
``jax.profiler`` (XLA/TPU timeline in TensorBoard / Perfetto):

- ``start_profiler_server(port)`` — expose the live profiling gRPC
  endpoint so ``tensorboard --logdir`` or ``xprof`` can attach to a
  serving worker (``run.py --profiler-port``).
- ``trace_to(dir)`` — trace the enclosed block; the engine loop's
  ``dyn/<phase>`` annotations and the model's named scopes land in it
  (docs/observability.md "Engine phases and the device trace").
"""

from __future__ import annotations

import contextlib
import logging
import threading

logger = logging.getLogger(__name__)

_server_started = False
_lock = threading.Lock()


def start_profiler_server(port: int) -> bool:
    """Idempotently start the jax.profiler collection server. Returns
    False (with a log line) when the backend doesn't support it."""
    global _server_started
    with _lock:
        if _server_started:
            return True
        try:
            import jax

            jax.profiler.start_server(port)
            _server_started = True
            logger.info("jax profiler server on port %d", port)
            return True
        except Exception as e:  # noqa: BLE001 - profiling is best-effort
            logger.warning("profiler server failed to start: %s", e)
            return False


@contextlib.contextmanager
def trace_to(log_dir: str):
    """Context manager tracing the enclosed block into ``log_dir``
    (viewable in TensorBoard's profile plugin)."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
