"""Open loop: independent users, arrivals on a schedule whether or not
earlier requests have finished. Each chain is one request.

The schedule is one fixed cycle as long as the window: arrival gaps and
lengths come from the traffic file's ``schedule_seed``, not from the
run's seed. The run's seed turns the cycle (where in it the window
opens) and makes the text. So every run offers the same requests at the
same spacings, starting from another point, and the lead-in is the end
of the cycle played before the window. Reason (PERF.md, PR 23): with
lengths and gaps shuffled afresh by each seed, two seeds at one rate
differed by tens of percent in every tail, because which long prompts
meet which bursts decides how far the batch fills, and the step slows as
it fills; a bound under 10 % could not have told a change of the program
from a change of the seed.
"""

from __future__ import annotations

import random

from lib.textgen import arrival_times, stratified


def generate(traffic: dict, cell: dict, seed: int, seconds: float, text) -> dict:
    schedule = random.Random(int(traffic["schedule_seed"]))
    lead_in = float(traffic["lead_in_s"])
    times = arrival_times(float(cell["rate_per_s"]), seconds, schedule)
    prompts = stratified(traffic["prompt_tokens"], len(times), schedule)
    outputs = stratified(traffic["output_tokens"], len(times), schedule)
    turn = random.Random(seed).random() * seconds
    chains = []
    for t, p, o in zip(times, prompts, outputs):
        at = (t - turn) % seconds  # place in the window, 0 <= at < seconds
        starts = [lead_in + at]
        if at >= seconds - lead_in:  # the cycle's end also plays as the lead-in
            starts.append(lead_in + at - seconds)
        for s in starts:
            chains.append({
                "start_s": s,
                "requests": [
                    {"messages": text.user_message(p), "max_tokens": o, "gap_s": 0.0}
                ],
            })
    chains.sort(key=lambda c: c["start_s"])
    return {"lead_in_s": lead_in, "chains": chains}
