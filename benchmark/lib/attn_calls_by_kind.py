"""``lib/attn_calls.py`` for a model whose attention shapes DIFFER BY THE
LAYERS' FORM (mimo_v2_flash: 4 KV heads in a full layer and 8 in a sliding
one, keys of 192 and values of 128 in both), which that module's one
``head_dim`` and one ``num_key_value_heads`` cannot say.

THE WORK COUNTED IS THE MODEL'S at its published widths, whatever lane
layout implements it (a head padded to 256 lanes would read low, never over
100 %), from the same host counters (``dynamo_attn_key_positions_total
{kind}``, ``dynamo_attn_context_tokens_total{kind}``,
``dynamo_attn_query_tokens_total{kind}``; each summed over the layers of
the kind):

- operations: a visible query-key pair and query head costs the score over
  ``head_dim`` and the value sum over ``v_head_dim``: ``2 * (head_dim +
  v_head_dim)``; the sink's one further column a query is not counted;
- bytes: a row's visible context once a dispatch and layer, at the FORM's
  KV heads x (``head_dim`` + ``v_head_dim``) values a token; a query's row
  in at ``heads * head_dim`` and its result out at ``heads * v_head_dim``.
"""

from __future__ import annotations


def shape(hf: dict, form: str) -> tuple[int, int, int, int]:
    """(query heads, the form's KV heads, a head's key width, its value
    width) of a configuration's file."""
    d = int(hf["head_dim"])
    kv = int(hf["num_key_value_heads"])
    if form == "sliding":
        kv = int(hf.get("swa_num_key_value_heads", kv))
    return int(hf["num_attention_heads"]), kv, d, int(hf.get("v_head_dim", d))


def operations(key_positions: float, hf: dict, form: str) -> float:
    heads, _kv, d, dv = shape(hf, form)
    return key_positions * 2 * (d + dv) * heads


def bytes_moved(context_tokens: float, query_tokens: float, hf: dict, form: str,
                itemsize: int = 2) -> float:
    heads, kv, d, dv = shape(hf, form)
    return itemsize * (context_tokens * kv * (d + dv) + query_tokens * heads * (d + dv))


def least_seconds(key_positions: float, context_tokens: float, query_tokens: float,
                  hf: dict, peak: dict, form: str) -> tuple[float, str]:
    """The least time the counted attention of the layers of ``form`` can
    take on a chip with ``peak`` (an entry of ``peaks.json``), and which
    bound sets it."""
    by_hbm = bytes_moved(context_tokens, query_tokens, hf, form) / (peak["hbm_gbps"] * 1e9)
    by_mxu = operations(key_positions, hf, form) / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")
