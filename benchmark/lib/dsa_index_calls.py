"""What a learned-sparse-attention model's indexer has to do to score a
dispatch's queries against their contexts, and what a chip needs at the
least to do it (``dynamo_exp_tpu/ops/sparse_index.py::index_scores``: ``I[t,
s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``).

- operations: a query-context pair costs one dot product of ``index_head_dim``
  a head: ``2 * index_n_heads * index_head_dim`` (the cut at zero, the
  weight and the sum over heads are a 128th of that and not counted);
  times the layers;
- bytes: each row's context index keys once a dispatch and layer at the
  pool's real row bytes, a query token's ``q_I`` (``heads x head_dim``
  values) and weights (``heads`` float32) once a layer, and the scores
  written once (4 B a pair: the selection reads them).

Pairs, context tokens and query tokens come from the program's counters
(``dynamo_dsa_index_pairs_total``, ``dynamo_mla_context_tokens_total``,
``dynamo_mla_query_tokens_total``: a layer's each). The first counts every
causal pair, also those of a stream part that skips the kernel because no
query there sees more than ``index_topk`` positions (a prompt's first
chunk): the least time reads that much high.
"""

from __future__ import annotations


def operations(pairs: float, hf: dict) -> float:
    a_pair = 2 * int(hf["index_n_heads"]) * int(hf["index_head_dim"])
    return pairs * a_pair * int(hf["num_hidden_layers"])


def bytes_moved(pairs: float, context_tokens: float, query_tokens: float, hf: dict,
                key_bytes: int, itemsize: int = 2) -> float:
    heads, width = int(hf["index_n_heads"]), int(hf["index_head_dim"])
    a_query = heads * width * itemsize + heads * 4
    return int(hf["num_hidden_layers"]) * (
        context_tokens * key_bytes + query_tokens * a_query + pairs * 4
    )


def least_seconds(pairs: float, context_tokens: float, query_tokens: float, hf: dict,
                  key_bytes: int, peak: dict) -> tuple[float, str]:
    """The least time the counted scoring can take on a chip with ``peak``
    (an entry of ``peaks.json``), and which bound sets it."""
    by_hbm = bytes_moved(pairs, context_tokens, query_tokens, hf, key_bytes) / (
        peak["hbm_gbps"] * 1e9
    )
    by_mxu = operations(pairs, hf) / (peak["bf16_tflops"] * 1e12)
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "bf16")
