"""Conversations replayed from a script. Each tenant has one fixed
system prompt; a session is 3-8 turns; the history sent with turn k+1 is
the script's own text for turns 1..k (as in the Mooncake traces), not
what the model answered, so the request set is the same whatever the
weights say. The next turn is due an exponential think time after the
last one ended.

Two ways to offer them, by what the cell's file gives. ``seats``: a fixed
population of users, each always in a conversation (a closed system: the
load follows the server, so the number in flight holds steady).
``rate_per_s``: conversations arrive as a Poisson process (open).

In the open form, sessions under way at schedule time 0 are drawn by simulating arrivals
before 0 with ``steady_state.turn_s_estimate`` seconds a turn; they join
spread over the lead-in, each at the turn it had reached, so the window
opens on a population of young and old sessions, not on a cold start.

The script's structure (who arrives when, how many turns, every length,
every think time) comes from the traffic file's ``schedule_seed``; the
run's seed makes only the text, system prompts included. Every run then
replays the same conversations with other words, and two seeds differ as
two runs of one seed do (see generators/open_loop.py for the reason).
"""

from __future__ import annotations

import random

from lib.textgen import arrival_times, quantile, stratified


def _session(rng, traffic, text, system, first_turn: int, turns: int, users, outs):
    """Requests for turns first_turn..turns-1 of one scripted session."""
    history = [{"role": "system", "content": system}]
    reqs = []
    for k in range(turns):
        user = text.text(users[k])
        if k >= first_turn:
            reqs.append(
                {
                    "messages": history + [{"role": "user", "content": user}],
                    "max_tokens": outs[k],
                    "gap_s": 0.0 if k == first_turn else quantile(
                        {"dist": "exponential", "mean": traffic["think_s_mean"]},
                        min(rng.random(), 0.999),
                    ),
                }
            )
        history = history + [
            {"role": "user", "content": user},
            {"role": "assistant", "content": text.text(outs[k])},
        ]
    return reqs


def _seats(traffic: dict, cell: dict, seconds: float, text, rng) -> dict:
    """A fixed population: ``seats`` users, each holding one conversation
    at a time and opening a new one (same tenant) a think time after the
    last ended. The first conversation of a seat starts at a turn drawn
    from its length, its history already in the prompt, and joins during
    the first half of the lead-in."""
    lead_in = float(traffic["lead_in_s"])
    seats = int(cell["seats"])
    tmin, tmax = traffic["turns"]["min"], traffic["turns"]["max"]
    systems = [text.text(traffic["system_prompt_tokens"]) for _ in range(traffic["tenants"])]
    # A seat's script must outlast the window however fast the server gets:
    # no turn takes less than the think time before it, on average.
    per_seat = int((lead_in + seconds) / (tmin * float(traffic["think_s_mean"]))) + 1
    n = seats * per_seat
    n_turns = stratified({"dist": "uniform_int", "min": tmin, "max": tmax}, n, rng)
    users = stratified(traffic["user_tokens"], n * tmax, rng)
    outs = stratified(traffic["output_tokens"], n * tmax, rng)
    think = {"dist": "exponential", "mean": traffic["think_s_mean"]}
    chains = []
    for seat in range(seats):
        reqs = []
        for j in range(per_seat):
            i = seat * per_seat + j
            first = rng.randrange(n_turns[i]) if j == 0 else 0
            session = _session(
                rng, traffic, text, systems[seat % len(systems)], first, n_turns[i],
                users[i * tmax:(i + 1) * tmax], outs[i * tmax:(i + 1) * tmax],
            )
            if j:  # the user thinks before opening the next conversation too
                session[0]["gap_s"] = quantile(think, min(rng.random(), 0.999))
            reqs += session
        chains.append({"start_s": lead_in * 0.5 * seat / seats, "requests": reqs})
    return {"lead_in_s": lead_in, "chains": chains}


def generate(traffic: dict, cell: dict, seed: int, seconds: float, text) -> dict:
    rng = random.Random(int(traffic["schedule_seed"]))
    if "seats" in cell:
        return _seats(traffic, cell, seconds, text, rng)
    lead_in = float(traffic["lead_in_s"])
    rate = float(cell["rate_per_s"])
    tmin, tmax = traffic["turns"]["min"], traffic["turns"]["max"]
    systems = [text.text(traffic["system_prompt_tokens"]) for _ in range(traffic["tenants"])]
    turn_s = float(traffic["steady_state"]["turn_s_estimate"])
    past = tmax * turn_s  # no session started earlier is still alive
    horizon = past + lead_in + seconds
    starts = [t - past for t in arrival_times(rate, horizon, rng)]
    n = len(starts)
    n_turns = stratified({"dist": "uniform_int", "min": tmin, "max": tmax}, n, rng)
    users = stratified(traffic["user_tokens"], n * tmax, rng)
    outs = stratified(traffic["output_tokens"], n * tmax, rng)
    chains = []
    for i, (start, turns) in enumerate(zip(starts, n_turns)):
        first_turn, at = 0, start
        if start < 0:
            first_turn = int(-start // turn_s)
            if first_turn >= turns:
                continue  # ended before the schedule starts
            at = rng.random() * lead_in
        reqs = _session(
            rng, traffic, text, systems[i % len(systems)], first_turn, turns,
            users[i * tmax:(i + 1) * tmax], outs[i * tmax:(i + 1) * tmax],
        )
        chains.append({"start_s": at, "requests": reqs})
    chains.sort(key=lambda c: c["start_s"])
    return {"lead_in_s": lead_in, "chains": chains}
