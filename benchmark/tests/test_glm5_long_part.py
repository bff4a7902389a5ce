"""``comparisons/sparse_attention.py``'s parts 3 and 4 at tiny sizes, float32,
through both of the program's selectors: the kernels' (interpreted here, a
stream of more than one slab, so a compared query lies in one slab of
several) and the gather's. The sets the run hands out (``kept_of``) are the
reference's, and the reference given them agrees with the engine's logits
to rounding."""

import os
import sys

import pytest

from comparisons import sparse_attention
from references import glm_moe_dsa

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, os.pardir, "tests"))
import test_glm_dsa as tiny  # noqa: E402  (tier-1's preset, its key file and its engine)


@pytest.mark.parametrize("impl", ["kernels", "gather"])
def test_the_compared_sets_are_the_runs_own(impl, monkeypatch):
    from dynamo_exp_tpu.models import llama

    monkeypatch.setattr(llama, "LATENT_SLAB", 32)  # 56 slots a dispatch: two slabs
    engine = tiny._engine(
        **({"attention_impl": "pallas", "pallas_interpret": True} if impl == "kernels" else {})
    )
    ctx = {
        "engine": engine, "params": engine.params, "hf": tiny.HF, "seed": 5,
        "reference": glm_moe_dsa,
    }
    out = sparse_attention.long_part(ctx, [0, 1], lengths=(120, 40), chunk=32)
    assert [(c["layer"], c["tokens"]) for c in out["long"]] == [(0, 120), (0, 40), (1, 120), (1, 40)]
    for c in out["long"]:
        assert c["rows_selecting"] > 10 and c["dropped_at_topk_plus_1"] == 1
        assert c["selecting_rows_keep_topk"] and c["unselecting_rows_keep_all"]
        assert c["min_selection_share"] == 1.0 and c["min_selection_share_decode"] == 1.0
    assert 0 < out["long_max_rel_err"] < 1e-5
