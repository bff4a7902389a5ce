"""The comparison a configuration gets when its file names none: the
largest difference between the served top log-probabilities and the
reference's, over two seeded probes of 8 decode steps each
(``lib/correct.py::walk_reference``), against the reference module's
``LOGPROB_TOL`` (tolerance and reason in that module). Right for a dense
model; a routed one needs ``routed_experts`` (why: that module)."""
from lib import correct

PROBES = 2


def compare(ctx: dict) -> dict:
    tol = ctx["reference"].LOGPROB_TOL
    walk = correct.walk_reference(ctx, PROBES)
    if "why" in walk:
        return {"ok": False, "why": walk["why"]}
    worst = max(walk["step_diffs"])
    return {
        "ok": worst <= tol, "max_abs_logprob_diff": worst, "tol": tol,
        "steps": len(walk["step_diffs"]),
        "why": None if worst <= tol else f"max |diff| {worst:.4f} > tol {tol}",
    }
