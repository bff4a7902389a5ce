"""Engine-facing request/response types shared by all frontends.

Capability parity with the reference's common protocol layer
(``/root/reference/lib/llm/src/protocols/common.rs``): stop conditions,
sampling options, the preprocessed ``BackendInput`` handed to engines, and
the per-step ``LLMEngineOutput`` engines stream back.
"""

from __future__ import annotations

import enum
from typing import Any

from pydantic import BaseModel, Field


PRIORITY_LOW = 0
PRIORITY_NORMAL = 1
PRIORITY_HIGH = 2

_PRIORITY_NAMES = {
    "low": PRIORITY_LOW,
    "normal": PRIORITY_NORMAL,
    "high": PRIORITY_HIGH,
}
_NAME_BY_PRIORITY = {v: k for k, v in _PRIORITY_NAMES.items()}


def parse_priority(raw) -> int:
    """Normalize a request's priority class to 0/1/2 (low/normal/high).

    Accepts the class names (case-insensitive) or their integers;
    ``None`` means ``normal``. Anything else raises ``ValueError`` (the
    HTTP layer maps it to 400) — a client that *tried* to prioritize
    deserves to know the spelling was wrong, not a silent ``normal``."""
    if raw is None:
        return PRIORITY_NORMAL
    if isinstance(raw, bool):  # bool is an int subclass; reject explicitly
        raise ValueError(f"invalid priority: {raw!r}")
    if isinstance(raw, int):
        if raw in _NAME_BY_PRIORITY:
            return raw
        raise ValueError(
            f"invalid priority: {raw!r} (expected 0..2 or low/normal/high)"
        )
    if isinstance(raw, str):
        name = raw.strip().lower()
        if name in _PRIORITY_NAMES:
            return _PRIORITY_NAMES[name]
        if name.lstrip("-").isdigit():
            return parse_priority(int(name))
    raise ValueError(
        f"invalid priority: {raw!r} (expected 0..2 or low/normal/high)"
    )


def priority_name(priority: int) -> str:
    return _NAME_BY_PRIORITY.get(priority, str(priority))


class FinishReason(str, enum.Enum):
    EOS = "eos"
    STOP = "stop"
    LENGTH = "length"
    CANCELLED = "cancelled"
    ERROR = "error"

    def to_openai(self) -> str:
        return {
            FinishReason.EOS: "stop",
            FinishReason.STOP: "stop",
            FinishReason.LENGTH: "length",
            FinishReason.CANCELLED: "stop",
            FinishReason.ERROR: "error",
        }[self]


class StopConditions(BaseModel):
    """When to stop generating."""

    max_tokens: int | None = None
    stop: list[str] = Field(default_factory=list)  # hidden stop strings
    stop_token_ids: list[int] = Field(default_factory=list)
    min_tokens: int | None = None
    ignore_eos: bool = False

    def apply_defaults(self, max_tokens_default: int | None) -> None:
        if self.max_tokens is None:
            self.max_tokens = max_tokens_default


class SamplingOptions(BaseModel):
    """How to pick the next token."""

    n: int = 1
    temperature: float | None = None
    top_p: float | None = None
    top_k: int | None = None
    frequency_penalty: float | None = None
    presence_penalty: float | None = None
    repetition_penalty: float | None = None
    seed: int | None = None
    logprobs: int | None = None


class BackendInput(BaseModel):
    """The fully preprocessed request handed to an execution engine:
    token ids in, token ids out. This is the seam between the serving
    stack and any engine implementation (TPU, echo, remote)."""

    token_ids: list[int]
    stop_conditions: StopConditions = Field(default_factory=StopConditions)
    sampling_options: SamplingOptions = Field(default_factory=SamplingOptions)
    annotations: list[str] = Field(default_factory=list)
    # Router hint: estimated prefix-cache overlap blocks on the chosen worker.
    estimated_prefix_hit_num_blocks: int | None = None
    # Disaggregation: set when a remote prefill worker already computed the
    # prompt's KV; the decode engine skips prefill for those blocks.
    remote_prefill: dict[str, Any] | None = None
    # Resumable streams: this request is a mid-stream failover
    # continuation and the last ``resume_offset`` entries of
    # ``token_ids`` are journaled *completion* tokens being re-prefilled,
    # not prompt. Engines treat the request normally (one batched prefill
    # over the whole sequence); the field marks the re-prefill hop for
    # telemetry and accounting — the journaling router owns usage fixup.
    resume_offset: int | None = None
    # Admission-control priority class (0=low, 1=normal, 2=high). The
    # edge sheds low first under load; the engine preempts the
    # lowest-priority ACTIVE sequence first under KV pressure.
    priority: int = 1

    def to_dict(self) -> dict:
        return self.model_dump(exclude_none=True)


class LLMEngineOutput(BaseModel):
    """One streamed step from an engine (token-level, pre-detokenization)."""

    token_ids: list[int] = Field(default_factory=list)
    # Engines that do their own detokenization may set text directly.
    text: str | None = None
    cum_log_probs: float | None = None
    # Per-token logprobs, aligned with token_ids (present only when the
    # request asked): chosen-token logprob, and the top-N alternatives
    # as {token_id: logprob} (N = the request's top_logprobs).
    logprobs: list[float] | None = None
    top_logprobs: list[dict[int, float]] | None = None
    # A model that generates by diffusion over blocks, with logprobs asked:
    # the denoising pass (0-based) that fixed each token, whose
    # distribution its log-probability is of. Aligned with token_ids.
    fixed_at_pass: list[int] | None = None
    finish_reason: FinishReason | None = None
    # Usage accounting, set on the final frame.
    prompt_tokens: int | None = None
    completion_tokens: int | None = None

    def to_dict(self) -> dict:
        return self.model_dump(exclude_none=True)

    @classmethod
    def from_dict(cls, d: dict) -> "LLMEngineOutput":
        return cls.model_validate(d)
