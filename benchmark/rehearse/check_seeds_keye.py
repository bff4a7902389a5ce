#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``sparse_attention_gqa``
needs: the same command line, the same output file.

    python3 benchmark/rehearse/check_seeds_keye.py \\
        --workload keye-vl-2.0-30b-a3b.longctx-saturate --tag keye-seeds \\
        --seeds 11,12,13 --control dense,top2047,index_keys_fp8 --control-seeds 11

Three are made on the ENGINE's side (``references/keye_vl2.py`` stays plain
and sound): what a token's row holds is rounded to float8 (e4m3) as it is
cached, behind an ``optimization_barrier`` (``_fp8``). A bare ``astype``
round trip on V read SOUND on the chip (V rows 0.00262 to the digit, first
among the controls or last; the CPU reads 0.037): XLA:TPU fuses the two
converts into the V projection as its epilogue (``convolution -> convert
f8e4m3fn -> convert bf16`` in ONE fusion of the optimized program, on the
chip and compiled for a described v5e alike), and a narrowing that is not a
fusion's RESULT does not reach the values. K's pair follows the rotary in a
fusion whose result IS the float8 array, so K was rounded all along. Behind
the barrier V's float8 value is a fusion's result too: V rows 0.0291-0.0299
(my chip runs, PR 61).

- ``index_keys_fp8``: the index key: the lower-precision control of the
  third cached thing (part 2's index rows, part 3's share of positions);
- ``k_fp8``: the heads' keys: THE LOWER-PRECISION CONTROL of the cached K
  rows (part 2) and of the scores (part 4);
- ``v_fp8``: the values: that of the cached V rows (part 2).

As ``--control`` they reach the comparison's parts 1-5, which call
``models/llama.py`` anew; the served walk (part 6) then runs the programs
the server compiled before, and reads sound. ``--served-fault NAME`` plants
one of the three BEFORE the server is built instead, so that every program
the probes make the server compile carries it: its ``sound`` entries are
then the readings of a faulty SERVED path, which is what ``MEDIAN_TOL`` and
``LONG_MEDIAN_TOL`` are held against.

The others are made on the REFERENCE's side, as ``check_seeds.py``'s own are
(the reference computes with the fault against the sound program):

- ``top2047`` (ENGINE's side: the comparison's one-layer model selects
  ``topk - 1``, so a row's set is one position short of what the
  configuration says);
- ``dense``: no selection, every query attends all it sees;
- ``top1024``: half of ``topk``;
- ``no_relu``: the index score's products not cut at zero;
- ``sections_permuted``: ``mrope_section`` [24, 24, 16]: the comparison's
  part 5 feeds unequal position components, where it fails (parts 1-4 and
  the served walk are text, whose components are equal, and read sound);
- ``index_not_rotary``: the index queries and keys not turned;
- ``no_layernorm``: the index key without its LayerNorm;
- ``no_qk_norms``: q and k without their per-head norms;
- ``fp8_experts``, ``top1``: ``check_seeds.py``'s.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402
from check_seeds_glm5 import _patched, _reference_with  # noqa: E402

ROUNDED = ("index_keys_fp8", "k_fp8", "v_fp8")  # ``engine_fault``'s
ENGINE_SIDE = (*ROUNDED, "top2047")
CONSTANTS = {
    "dense": {"topk": 10**9}, "top1024": None, "no_relu": {"relu": False},
    "sections_permuted": None, "index_not_rotary": {"index_rope": False},
}
REFERENCE_SIDE = (*CONSTANTS, "no_layernorm", "no_qk_norms")
plain_control = check_seeds.control


def _fp8(x):
    """``x`` rounded to float8 (e4m3) and back, the narrow value behind a
    barrier so that it is some fusion's result (module docstring)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.optimization_barrier(x.astype(jnp.float8_e4m3fn)).astype(x.dtype)


@contextlib.contextmanager
def engine_fault(name: str, hf: dict):
    """``models/llama.py`` caching one part of a token's row (``k_fp8``,
    ``v_fp8``, ``index_keys_fp8``) rounded to float8, in every program traced
    while it stands."""
    from dynamo_exp_tpu.models import llama

    kl = int(hf["num_key_value_heads"]) * int(hf["head_dim"])

    def faulty(plain):
        def write(k_cache, v_cache, k_new, v_new, *rest, **kw):
            import jax.numpy as jnp

            if name == "v_fp8":
                v_new = _fp8(v_new)
            elif name == "k_fp8":
                k_new = jnp.concatenate([_fp8(k_new[:, :kl]), k_new[:, kl:]], axis=-1)
            else:
                k_new = jnp.concatenate([k_new[:, :kl], _fp8(k_new[:, kl:])], axis=-1)
            return plain(k_cache, v_cache, k_new, v_new, *rest, **kw)
        return write

    with _patched(llama, "write_kv_pages", faulty):
        yield


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name``."""
    reference = ctx["reference"]
    if name == "top2047":
        import dataclasses

        from comparisons import sparse_attention_gqa as gqa

        def faulty(plain):
            def model(engine):
                one, build = plain(engine)
                return dataclasses.replace(one, index_topk=one.index_topk - 1), build
            return model
        with _patched(gqa, "_one_layer_model", faulty):
            yield ctx
    elif name in ROUNDED:
        with engine_fault(name, ctx["hf"]):
            yield ctx
    elif name in CONSTANTS:
        def faulty(plain):
            def constants(hf):
                c = plain(hf)
                fault = CONSTANTS[name] or {
                    "top1024": {"topk": c["topk"] // 2},
                    "sections_permuted": {"sections": c["sections"][1:] + c["sections"][:1]},
                }[name]
                return {**c, **fault}
            return constants
        with _reference_with(reference, "block_constants", faulty):
            yield ctx
    elif name == "no_layernorm":
        with _reference_with(reference, "_layer_norm", lambda plain: lambda x, w, b, eps=0: x):
            yield ctx
    elif name == "no_qk_norms":
        def faulty(plain):
            return lambda x, w, eps: x if x.ndim == 3 else plain(x, w, eps)
        with _reference_with(reference, "_rms_norm", faulty):
            yield ctx
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


def _served_fault(argv: list[str]) -> str | None:
    """``--served-fault NAME`` taken out of ``argv`` (``check_seeds.main``
    parses the rest)."""
    if "--served-fault" not in argv:
        return None
    at = argv.index("--served-fault")
    name = argv[at + 1]
    del argv[at : at + 2]
    if name not in ROUNDED:
        raise SystemExit(f"--served-fault takes one of {ROUNDED}, not {name!r}")
    return name


if __name__ == "__main__":
    import run_cell

    check_seeds.CONTROLS = ENGINE_SIDE + REFERENCE_SIDE + ("fp8_experts", "top1")
    check_seeds.control = control
    served = _served_fault(sys.argv)
    with contextlib.ExitStack() as stack:
        if served:
            workload = sys.argv[sys.argv.index("--workload") + 1]
            stack.enter_context(engine_fault(served, run_cell.load_cell(workload)["config"]))
        code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
