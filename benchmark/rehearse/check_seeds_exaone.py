#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``sliding_attention``
needs: the same command line, the same output file.

    python3 benchmark/rehearse/check_seeds_exaone.py \\
        --workload k-exaone-236b.mixedlen-saturate --tag exaone-seeds \\
        --seeds 11,12,13 --control width_127,width_129,rope_on_full,released_early,\\
released_early_engine,k_fp8 --control-seeds 11

Three are made on the ENGINE's side (``references/exaone_moe.py`` stays plain
and sound; the comparison's parts 1-3 call ``models/llama.py`` anew, the
served walk runs the programs the server compiled before):

- ``released_early``: part 3 gives a row's sliding pages back one page too
  early (``comparisons/sliding_attention.py::engine_long``), so that a
  query reads a page another row has written since;
- ``released_early_engine``: the SERVED engine's own pool does
  (``Scheduler.release_sliding_behind`` gives back through it after every
  consumed dispatch), so that part 4's long probe reads pages it no longer
  holds through the engine's compiled programs and page tables: the one
  fault here that only the served walk can see;
- ``k_fp8``: K rounded to float8 (e4m3) as it is cached, in both pools: THE
  LOWER-PRECISION CONTROL (part 2's rows, part 3's logits).

The others are made on the REFERENCE's side, as ``check_seeds.py``'s own
are (the reference computes with the fault against the sound program):

- ``width_127`` / ``width_129``: the sliding width off by one either way;
- ``rope_on_full``: rotation applied on the full layers too;
- ``no_bias``: the experts chosen by the scores without the selection bias;
- ``fp8_experts``, ``top1``: ``check_seeds.py``'s.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402
from check_seeds_glm5 import _patched, _reference_with  # noqa: E402

ENGINE_SIDE = ("released_early", "released_early_engine", "k_fp8")
REFERENCE_SIDE = ("width_127", "width_129", "rope_on_full", "no_bias")
plain_control = check_seeds.control


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name``."""
    from dynamo_exp_tpu.models import llama

    reference = ctx["reference"]
    if name == "released_early":
        yield {**ctx, "control": name}
    elif name == "released_early_engine":
        pool = ctx["engine"].kv.sliding
        plain = pool.release_behind
        pool.release_behind = lambda ids, first, next_pos: plain(
            ids, first, next_pos + pool.page_size
        )
        try:
            yield ctx
        finally:
            del pool.release_behind
    elif name == "k_fp8":
        def faulty(plain):
            def write(k_cache, v_cache, k_new, v_new, *rest, **kw):
                return plain(k_cache, v_cache, check_seeds._fp8(k_new), v_new, *rest, **kw)
            return write
        with _patched(llama, "write_kv_pages", faulty):
            yield ctx
    elif name in ("width_127", "width_129", "rope_on_full"):
        def faulty(plain):
            def constants(hf):
                c = plain(hf)
                if name == "rope_on_full":
                    return {**c, "rotary": (True,) * len(c["rotary"])}
                return {**c, "width": c["width"] + (1 if name == "width_129" else -1)}
            return constants
        with _reference_with(reference, "block_constants", faulty):
            yield ctx
    elif name == "no_bias":
        def faulty(plain):
            return lambda g, bias, **kw: plain(g, 0 * bias, **kw)
        with _reference_with(reference, "_route", faulty):
            yield ctx
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


if __name__ == "__main__":
    check_seeds.CONTROLS = ENGINE_SIDE + REFERENCE_SIDE + ("fp8_experts", "top1")
    check_seeds.control = control
    code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
