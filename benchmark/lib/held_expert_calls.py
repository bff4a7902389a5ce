"""What the grouped-matmul kernel has to do for a model that HOLDS A SHARE
of the experts its router scores (``dynamo_exp_tpu/ops/moe.py``, "a share of
the experts"), and what a chip needs at the least to do it.

``lib/expert_calls.py`` reckons a call from its shapes alone: every one of
its ``M`` rows meets an expert and ``min(E, M)`` experts have a row. Of a
held share neither holds: the picks that land elsewhere never reach the
kernel, about half of a call's rows are live (``ops/moe.py::
held_block_rows`` gathers twice an even router's share), and a router that
crowds its picks (seeded random weights do: the rows of a dispatch share
most of their hidden state, hence most of their experts) leaves most held
experts without a row, whose matrices the kernel never fetches. Counted by
the shapes such a run read 197 % of its roofline (my chip run, PR 44).

So the work comes from what the program counts ON THE DEVICE
(``dynamo_moe_picks_total{where="held"}``: the live rows that reach a held
expert; ``dynamo_moe_held_expert_reads_total``: the held experts that had
one, a layer and dispatch), three matrix products a pick (gate, up, down):

- operations: ``3 * 2 * picks * K * N``;
- bytes: ``3 * reads`` matrices of ``K x N`` (each at least once: a group
  that straddles two row blocks is fetched twice, which this does not
  count, so the share reads low, never high) and a pick's rows in and out
  of the three products, ``3 * (K + N)`` values.
"""

from __future__ import annotations


def operations(picks: float, k: int, n: int) -> float:
    return 3 * 2 * picks * k * n


def bytes_moved(picks: float, reads: float, k: int, n: int, itemsize: int = 2) -> float:
    return itemsize * (3 * reads * k * n + 3 * picks * (k + n))


def least_seconds(picks: float, reads: float, k: int, n: int, peak: dict) -> tuple[float, str]:
    """The least time the counted work can take on a chip with ``peak`` (an
    entry of ``peaks.json``), and which bound sets it."""
    by_bytes = bytes_moved(picks, reads, k, n) / (peak["hbm_gbps"] * 1e9)
    by_ops = operations(picks, k, n) / (peak["bf16_tflops"] * 1e12)
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "mxu")
