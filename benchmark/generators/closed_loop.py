"""Closed loop: ``clients`` callers that each wait for a reply and send
the next request when the last one ends. The load follows the server, so
what is judged is the rate of work completed.

Lengths are drawn round by round from the traffic file's
``schedule_seed``: the k-th requests of all clients are one evenly spaced
sample of the distribution, so however many rounds a run gets through, it
has seen a balanced mix, and every run is offered the same sequences, by
the same clients, joining at the same times. The run's seed makes only
the text, as in generators/sessions.py.

Until PR 26 the seed also decided which client got which sequence. Two
runs of one seed then agreed to the last token (824.63 tokens/s twice)
while six seeds spread by 8 %: clients join over the first seconds of
the lead-in, so another assignment is another order of arrivals, the loop
never forgets it, and it decides how long the batch holds a context over
1k tokens, at which the program changes its attention form and the step
its cost (PERF.md section 6, PR 26). That was the draw, not the system.
"""

from __future__ import annotations

import random

from lib.textgen import stratified

# A client's chain must outlast the window: unless the traffic file says
# otherwise, this many requests a client for every second of the horizon
# (a mean chat request, 170 tokens out, would have to end in under 2 s to
# exhaust it). The generator stops sending at the window's end.
REQUESTS_PER_CLIENT_SECOND = 0.5


def generate(traffic: dict, cell: dict, seed: int, seconds: float, text) -> dict:
    schedule = random.Random(int(traffic["schedule_seed"]))
    lead_in = float(traffic["lead_in_s"])
    clients = int(cell["clients"])
    per_s = float(traffic.get("requests_per_client_s", REQUESTS_PER_CLIENT_SECOND))
    rounds = max(4, int((lead_in + seconds) * per_s))
    sequences: list[list] = [[] for _ in range(clients)]
    for _ in range(rounds):
        prompts = stratified(traffic["prompt_tokens"], clients, schedule)
        outputs = stratified(traffic["output_tokens"], clients, schedule)
        for seq, p, o in zip(sequences, prompts, outputs):
            seq.append((p, o))
    chains = []
    for c in range(clients):
        reqs = [
            {"messages": text.user_message(p), "max_tokens": o, "gap_s": 0.0}
            for p, o in sequences[c]
        ]
        # Clients join over the first fifth of the lead-in, not at once.
        chains.append({"start_s": lead_in * 0.2 * c / clients, "requests": reqs})
    return {"lead_in_s": lead_in, "chains": chains}
