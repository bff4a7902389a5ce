"""What a grouped-query model under a learned selection (KeyeVL2:
``sa_config``) has to do in its indexer and in its attention, and what a
chip needs at the least to do it, from the configuration's own keys.

THE INDEXER (``dynamo_exp_tpu/ops/sparse_index.py::index_scores``: ``I[t, s] =
sum_j w[t, j] relu(q_I[t, j] . k_I[s])``):

- operations: a query-context pair costs one dot product of
  ``indexer_head_dim`` a head: ``2 * indexer_num_heads * indexer_head_dim``
  (the cut at zero, the weight and the sum over heads are a 64th of that and
  not counted), times the layers;
- bytes: each row's context index keys once a dispatch and layer at the
  lanes the key takes in the cache (``key_bytes``), a query token's ``q_I``
  and weights once a layer, and the scores written once (4 B a pair).

ATTENTION over the SELECTED pairs (the model's work, whatever computes it):

- operations: a selected query-key pair and query head costs the score and
  the value sum over ``head_dim``: ``4 * head_dim``;
- bytes: a row's visible context once a dispatch and layer at the KV heads'
  K and V (``2 * num_key_value_heads * head_dim`` values a token), a query's
  heads in and its result out.

Pairs come a layer's from the program's counters
(``dynamo_dsa_index_pairs_total``, ``dynamo_dsa_selected_pairs_total``);
context and query tokens SUMMED OVER THE LAYERS
(``dynamo_attn_context_tokens_total{kind="full"}``,
``dynamo_attn_query_tokens_total{kind="full"}``).
"""

from __future__ import annotations


def _layers(hf: dict) -> int:
    return int(hf["num_hidden_layers"])


def index_operations(pairs: float, hf: dict) -> float:
    sa = hf["sa_config"]
    return pairs * 2 * int(sa["indexer_num_heads"]) * int(sa["indexer_head_dim"]) * _layers(hf)


def index_bytes(pairs: float, context_tokens: float, query_tokens: float, hf: dict,
                key_bytes: int, itemsize: int = 2) -> float:
    """``context_tokens`` and ``query_tokens`` summed over the layers,
    ``pairs`` a layer's."""
    sa = hf["sa_config"]
    heads, width = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    a_query = heads * width * itemsize + heads * 4
    return context_tokens * key_bytes + query_tokens * a_query + pairs * 4 * _layers(hf)


def attn_operations(selected_pairs: float, hf: dict) -> float:
    return (
        selected_pairs * 4 * int(hf["head_dim"]) * int(hf["num_attention_heads"]) * _layers(hf)
    )


def attn_bytes(context_tokens: float, query_tokens: float, hf: dict, itemsize: int = 2) -> float:
    d = int(hf["head_dim"])
    kv, heads = int(hf["num_key_value_heads"]), int(hf["num_attention_heads"])
    return itemsize * (context_tokens * 2 * kv * d + query_tokens * 2 * heads * d)


def least_seconds(operations: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The least time counted work can take on a chip with ``peak`` (an
    entry of ``peaks.json``), and which bound sets it."""
    by_hbm = bytes_ / (peak["hbm_gbps"] * 1e9)
    by_mxu = operations / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")
