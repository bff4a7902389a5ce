"""What softmax attention over a paged cache has to do for the queries a
model with sliding layers was fed, by the layers' form, and what a chip
needs at the least to do it (``dynamo_exp_tpu/ops/ragged_attention.py``:
one kernel serves both forms, a sliding layer's call with its width).

THE WORK COUNTED IS THE MODEL'S, whatever implements it, from what the
engine counts on the host from each dispatch's plan
(``dynamo_attn_key_positions_total{kind}``: the key positions the queries
may see, ``t + 1`` in a full layer and ``min(t + 1, width)`` in a sliding
one; ``dynamo_attn_context_tokens_total{kind}``: the context tokens a row's
queries of one dispatch see between them; ``dynamo_attn_query_tokens_total
{kind}``; each summed over the layers of the kind):

- operations: a query-key pair and head costs the score and the value sum
  over ``head_dim``: ``2 * 2 * head_dim``; times the query heads;
- bytes: a row's visible context once a dispatch and layer, K and V at
  ``num_key_value_heads * head_dim`` values each, and a query's row in and
  its result out (``2 * heads * head_dim`` values). A kernel that fetches a
  row's context once for every query block of its span reads more, which
  this does not count: the share reads low, never high.
"""

from __future__ import annotations


def operations(key_positions: float, hf: dict) -> float:
    return key_positions * 4 * int(hf["head_dim"]) * int(hf["num_attention_heads"])


def bytes_moved(context_tokens: float, query_tokens: float, hf: dict, itemsize: int = 2) -> float:
    d = int(hf["head_dim"])
    a_token = 2 * int(hf["num_key_value_heads"]) * d
    a_query = 2 * int(hf["num_attention_heads"]) * d
    return itemsize * (context_tokens * a_token + query_tokens * a_query)


def least_seconds(key_positions: float, context_tokens: float, query_tokens: float,
                  hf: dict, peak: dict) -> tuple[float, str]:
    """The least time the counted attention can take on a chip with ``peak``
    (an entry of ``peaks.json``), and which bound sets it."""
    by_hbm = bytes_moved(context_tokens, query_tokens, hf) / (peak["hbm_gbps"] * 1e9)
    by_mxu = operations(key_positions, hf) / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")
