#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``latent_attention``
needs: the same command line, the same output file.

    python3 benchmark/rehearse/check_seeds_pangu.py \\
        --workload openpangu-ultra-moe-718b.longdoc-saturate --tag pangu-seeds \\
        --seeds 11,12,13 --control score_scale,latent_fp8 --control-seeds 11,12

Each control is made on the ENGINE's side (``references/openpangu_moe.py``
stays plain and sound): ``models/llama.py`` or ``ops/moe.py`` computes with
the fault in the comparison's parts 1-3, which call those functions anew;
the served walk (part 4) runs the programs the server compiled before the
fault was made and reads sound. So a control must fail by a limit of parts
1-3, and by no other.

- ``score_scale``: the scores scaled by ``1/sqrt(nope)`` (128), the rotary
  part's width left out of the head size;
- ``latent_before_norm``: ``c_kv`` cached (and attended) before its norm;
- ``key_before_rope``: ``k_r`` cached before RoPE;
- ``held_renorm``: a held pick weighed over the held picks' sum, not over
  all eight chosen;
- ``no_shared``: the shared expert left out;
- ``latent_fp8``: the cached row rounded to float8 (e4m3) once more than
  bfloat16: THE LOWER-PRECISION CONTROL of the cache;
- ``ukv_fp8``: ``W_ukv`` (what the absorbed form multiplies the queries and
  the latent outputs by) rounded to float8: the lower-precision control of
  the absorbed products;

Two more are ``check_seeds.py``'s own, made on the REFERENCE's side (a
second copy of the held experts does not fit beside the first, and the
served walk can only be reached from there): ``fp8_experts`` (the
reference's feed-forward matrices rounded to float8 where it takes them up,
an expert at a time) and ``top1`` (the reference choosing one expert a
token: a wrong routing rule, what the served walk's median is set against).
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402

OWN = (
    "score_scale", "latent_before_norm", "key_before_rope", "held_renorm", "no_shared",
    "latent_fp8", "ukv_fp8",
)
plain_control = check_seeds.control


@contextlib.contextmanager
def _patched(module, name: str, faulty):
    plain = getattr(module, name)
    setattr(module, name, faulty(plain))
    try:
        yield
    finally:
        setattr(module, name, plain)


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name`` in what the engine's side computes."""
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama
    from dynamo_exp_tpu.ops import moe

    mcfg = ctx["engine"].cfg.model
    params = ctx.get("engine_params") or ctx["engine"].params

    def with_layers(**rounded):
        layers = {**params["layers"], **rounded}
        return {**ctx, "engine_params": {**params, "layers": layers}}

    fp8 = check_seeds._fp8
    if name == "score_scale":
        def faulty(plain):
            return lambda cfg: {**plain(cfg), "sm_scale": cfg.qk_nope_head_dim ** -0.5}
        with _patched(llama, "_latent_read_args", faulty):
            yield ctx
    elif name == "latent_before_norm":
        def faulty(plain):  # kv_a_norm alone norms a row of this width
            return lambda x, w, eps, offset=False: (
                x if x.shape[-1] == mcfg.kv_lora_rank else plain(x, w, eps, offset)
            )
        with _patched(llama, "rms_norm", faulty):
            yield ctx
    elif name == "key_before_rope":
        def faulty(plain):  # the shared rotary key alone has one head
            return lambda x, pos, inv_freq: x if x.shape[-2] == 1 else plain(x, pos, inv_freq)
        with _patched(llama, "apply_rope", faulty):
            yield ctx
    elif name == "held_renorm":
        lo, held = mcfg.first_expert_held, mcfg.experts_held

        def faulty(plain):
            def router(x, w, k, norm=True, **kw):
                weights, ids = plain(x, w, k, False, **{**kw, "scale": 1.0})
                here = (ids >= lo) & (ids < lo + held)
                mine = jnp.where(here, weights, 0.0)
                mine = mine / (jnp.sum(mine, axis=-1, keepdims=True) + 1e-20)
                return mine * kw.get("scale", 1.0), ids
            return router
        with _patched(moe, "moe_router", faulty):
            yield ctx
    elif name == "no_shared":
        zero = {k: jnp.zeros_like(params["layers"][k]) for k in ("shared_down",)}
        yield with_layers(**zero)
    elif name == "latent_fp8":
        def faulty(plain):
            return lambda k_cache, v_cache, k_new, *rest, **kw: plain(
                k_cache, v_cache, fp8(k_new), *rest, **kw
            )
        with _patched(llama, "write_kv_pages", faulty):
            yield ctx
    elif name == "ukv_fp8":
        yield with_layers(w_ukv=fp8(params["layers"]["w_ukv"]))
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


if __name__ == "__main__":
    check_seeds.CONTROLS = OWN + ("fp8_experts", "top1")
    check_seeds.control = control
    code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
