#!/usr/bin/env python3
"""``check_seeds.py`` with the controls a block-diffusion model on routed
experts needs beside its own (``fp8_attention``, ``fp8_head``,
``fp8_experts``, ``fp8_weights``, ``top1``): the same command line, the
same output file.

    python3 benchmark/rehearse/check_seeds_sdar.py --workload sdar-30b-a3b.fixedlen-saturate \\
        --tag sdar-seeds --seeds 11,12,13 \\
        --control causal_bound,no_write_pass,shifted,no_renorm,fp8_experts --control-seeds 11,12

Each control is made on the reference's side (``references/sdar_moe.py``
stays plain): the reference computes with the fault against the sound
program.

- ``causal_bound``: a position sees those at or before it, not its block;
- ``no_write_pass``: an earlier block as its last denoising pass wrote it
  (the mask token where that pass fixed a position), not as the writing
  pass does;
- ``shifted``: a token's distribution read one position earlier, as a
  model that predicts the NEXT token would have it;
- ``no_renorm``: the top-8 weights not divided by their sum.
"""

from __future__ import annotations

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check_seeds  # noqa: E402

OWN = ("causal_bound", "no_write_pass", "shifted", "no_renorm")
plain_control = check_seeds.control


@contextlib.contextmanager
def _patched(reference, name: str, faulty):
    plain = getattr(reference, name)
    setattr(reference, name, faulty(plain))
    check_seeds._retrace(reference)
    try:
        yield
    finally:
        setattr(reference, name, plain)
        check_seeds._retrace(reference)


@contextlib.contextmanager
def control(name: str, ctx: dict):
    reference = ctx["reference"]
    if name == "causal_bound":
        causal = lambda _plain: lambda pos, block: pos[None, :] <= pos[:, None]  # noqa: E731
        with _patched(reference, "_sees", causal):
            yield ctx
    elif name == "no_write_pass":
        def faulty(_plain):
            def as_last_pass_left_it(tokens, passes, mask):
                last = max(passes)
                return [mask if s == last and s >= 0 else t for t, s in zip(tokens, passes)]
            return as_last_pass_left_it
        with _patched(reference, "written_block", faulty):
            yield ctx
    elif name == "shifted":
        def faulty(plain):
            return lambda params, hf, ids, rows: plain(
                params, hf, ids, [max(r - 1, 0) for r in rows]
            )
        with _patched(reference, "log_probs", faulty):
            yield ctx
    elif name == "no_renorm":
        yield {**ctx, "hf": {**ctx["hf"], "norm_topk_prob": False}}
    else:
        with plain_control(name, ctx) as faulty_ctx:
            yield faulty_ctx


if __name__ == "__main__":
    check_seeds.CONTROLS = check_seeds.CONTROLS + OWN
    check_seeds.control = control
    code = check_seeds.main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
