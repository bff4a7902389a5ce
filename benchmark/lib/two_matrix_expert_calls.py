"""``lib/held_expert_calls.py`` for experts of TWO matrices (nemotron_h:
``down(relu(up x)^2)``, no gate): what the grouped-matmul kernel has to do
for the picks the programs count on the device
(``dynamo_moe_picks_total{where="held"}``, ``dynamo_moe_held_expert_reads_
total``), two matrix products a pick (up, down) where that module counts
three (gate, up, down), which would read 1.5 x the truth here:

- operations: ``2 * 2 * picks * K * N``;
- bytes: ``2 * reads`` matrices of ``K x N`` (each at least once) and a
  pick's rows in and out of the two products, ``2 * (K + N)`` values.

``N`` is the experts' own width (1856), not the 1920 lanes they are stored
at (``ModelConfig.expert_stack_width``): the zeros the kernel multiplies are
what it spends beyond the model's work, so the share reads low, never high.
"""

from __future__ import annotations


def operations(picks: float, k: int, n: int) -> float:
    return 2 * 2 * picks * k * n


def bytes_moved(picks: float, reads: float, k: int, n: int, itemsize: int = 2) -> float:
    return itemsize * (2 * reads * k * n + 2 * picks * (k + n))


def least_seconds(picks: float, reads: float, k: int, n: int, peak: dict) -> tuple[float, str]:
    """The least time the counted work can take on a chip with ``peak`` (an
    entry of ``peaks.json``), and which bound sets it."""
    by_bytes = bytes_moved(picks, reads, k, n) / (peak["hbm_gbps"] * 1e9)
    by_ops = operations(picks, k, n) / (peak["bf16_tflops"] * 1e12)
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "mxu")
