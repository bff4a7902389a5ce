#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a model of ``sparse_attention``
needs: the same command line, the same output file.

    python3 benchmark/rehearse/check_seeds_glm5.py \\
        --workload glm-5.longctx-saturate --tag glm5-seeds \\
        --seeds 11,12,13 --control dense,top1024,index_keys_fp8 --control-seeds 11

Three are made on the ENGINE's side (``references/glm_moe_dsa.py`` stays
plain and sound; the comparison's parts 1-4 call ``models/llama.py`` anew,
the served walk runs the programs the server compiled before and reads
sound):

- ``ukv_fp8``: ``W_ukv`` rounded to float8 (e4m3): THE LOWER-PRECISION
  CONTROL of the absorbed products (part 4);
- ``index_keys_fp8``: the index key rounded to float8 as it is cached: the
  lower-precision control of the second pool (part 2's index rows, and
  part 3's share of positions);
- ``latent_fp8``: the latent row rounded to float8 as it is cached.

The others are made on the REFERENCE's side, as ``check_seeds.py``'s own
are (the reference computes with the fault against the sound program: the
distance of the faulty program from the sound reference, without tracing a
second program):

- ``dense``: no selection, every query attends all it sees;
- ``top1024``: half of ``index_topk``;
- ``no_relu``: the index score's products not cut at zero;
- ``no_weights``: every index head weighed 1;
- ``no_layernorm``: the index key without its LayerNorm;
- ``keys_before_rope``: the index key cached (and scored) before RoPE;
- ``no_bias``: the experts chosen by ``s`` without ``b``;
- ``fp8_experts``, ``top1``: ``check_seeds.py``'s.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402

ENGINE_SIDE = ("ukv_fp8", "index_keys_fp8", "latent_fp8")
REFERENCE_SIDE = (
    "dense", "top1024", "no_relu", "no_weights", "no_layernorm", "keys_before_rope", "no_bias",
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
def _reference_with(reference, name: str, faulty):
    try:
        with _patched(reference, name, faulty):
            check_seeds._retrace(reference)
            yield
    finally:
        check_seeds._retrace(reference)


@contextlib.contextmanager
def control(name: str, ctx: dict):
    """``ctx`` with the fault ``name``."""
    import jax.numpy as jnp

    from dynamo_exp_tpu.models import llama

    reference = ctx["reference"]
    params = ctx.get("engine_params") or ctx["engine"].params
    fp8 = check_seeds._fp8
    if name == "ukv_fp8":
        layers = {**params["layers"], "w_ukv": fp8(params["layers"]["w_ukv"])}
        yield {**ctx, "engine_params": {**params, "layers": layers}}
    elif name in ("index_keys_fp8", "latent_fp8"):
        def faulty(plain):
            def write(k_cache, v_cache, k_new, v_new, *rest, **kw):
                if name == "latent_fp8":
                    k_new = fp8(k_new)
                elif v_new is not None:
                    v_new = fp8(v_new)
                return plain(k_cache, v_cache, k_new, v_new, *rest, **kw)
            return write
        with _patched(llama, "write_kv_pages", faulty):
            yield ctx
    elif name == "dense":
        def faulty(plain):
            return lambda scores, q_pos, topk: (
                q_pos[:, None] >= jnp.arange(scores.shape[1])[None],
                jnp.full(q_pos.shape, jnp.inf),
            )
        with _reference_with(reference, "_select", faulty):
            yield ctx
    elif name == "top1024":
        def faulty(plain):
            return lambda scores, q_pos, topk: plain(scores, q_pos, topk // 2)
        with _reference_with(reference, "_select", faulty):
            yield ctx
    elif name == "no_relu":
        def faulty(plain):
            def scores(q_i, w_i, k_i, q_pos, c=None):
                raw = jnp.einsum("qjs,qj->qs", jnp.einsum("qjd,sd->qjs", q_i, k_i), w_i)
                seen = q_pos[:, None] >= jnp.arange(k_i.shape[0])[None, :]
                return jnp.where(seen, raw + 0.0, -jnp.inf)
            return scores
        with _reference_with(reference, "_index_scores", faulty):
            yield ctx
    elif name == "no_weights":
        def faulty(plain):
            def queries(*a):
                q, w = plain(*a)
                return q, jnp.ones_like(w)
            return queries
        with _reference_with(reference, "_index_queries", faulty):
            yield ctx
    elif name == "no_layernorm":
        with _reference_with(reference, "_layer_norm", lambda plain: lambda x, w, b, eps=0: x):
            yield ctx
    elif name == "keys_before_rope":
        def faulty(plain):
            return lambda u, lp, c, positions: reference._layer_norm(
                u @ reference._f32(lp["idx_wk"]), lp["idx_k_norm"], lp["idx_k_bias"]
            )
        with _reference_with(reference, "_index_keys", faulty):
            yield ctx
    elif name == "no_bias":
        def faulty(plain):
            return lambda s, bias, **kw: plain(s, 0 * bias, **kw)
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
