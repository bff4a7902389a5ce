"""The generators: the seed changes the text (and turns an open loop's
cycle), never the work; plans are deterministic; chains have the documented shape."""

import importlib
import json
import os
import random

import pytest

from lib import textgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeText:
    """Stands in for TextMaker: the wanted length is the content."""

    def text(self, n):
        return f"<{n}>"

    def user_message(self, n):
        return [{"role": "user", "content": f"<{n}>"}]


def load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def plan(gen, traffic, cell, seed, seconds=30.0):
    return importlib.import_module(f"generators.{gen}").generate(
        load("traffic", traffic), cell, seed, seconds, FakeText()
    )


def test_stratified_is_one_set_in_seeded_order():
    dist = {"dist": "lognormal", "median": 160, "sigma": 1.0, "min": 16, "max": 2048}
    a = textgen.stratified(dist, 200, random.Random(1))
    b = textgen.stratified(dist, 200, random.Random(2))
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 16 and max(a) <= 2048
    assert 140 <= sorted(a)[100] <= 180  # the median is the median


def test_arrivals_fill_the_horizon_with_the_same_gaps_for_every_seed():
    a = textgen.arrival_times(4.0, 50.0, random.Random(1))
    b = textgen.arrival_times(4.0, 50.0, random.Random(2))
    assert len(a) == len(b) == 200
    assert all(0 <= t < 50.0 for t in a) and a == sorted(a) and a != b


def shape(pl):
    """A plan without its text: when each request is due and how long."""
    return [
        (round(c["start_s"], 6), [(r["max_tokens"], round(r["gap_s"], 6)) for r in c["requests"]])
        for c in pl["chains"]
    ]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_open_loop_is_one_cycle_turned_by_the_seed(seed):
    p = plan("open_loop", "chat-steady", {"rate_per_s": 4.0}, seed)
    q = plan("open_loop", "chat-steady", {"rate_per_s": 4.0}, 99)
    lead = p["lead_in_s"]

    def window(pl):
        return [c for c in pl["chains"] if c["start_s"] >= lead]

    def work(pl):
        return sorted(c["requests"][0]["max_tokens"] for c in window(pl))

    assert work(p) == work(q) and len(window(p)) == 120  # the same requests, all of them
    assert [c["start_s"] for c in window(p)] != [c["start_s"] for c in window(q)]  # turned
    assert all(lead <= c["start_s"] < lead + 30.0 for c in window(p))
    # The lead-in replays the end of the cycle: same lengths, 30 s earlier.
    tail = {(round(c["start_s"] - 30.0, 6), c["requests"][0]["max_tokens"])
            for c in window(p) if c["start_s"] >= 30.0}
    head = {(round(c["start_s"], 6), c["requests"][0]["max_tokens"])
            for c in p["chains"] if c["start_s"] < lead}
    assert head == tail and head
    assert all(len(c["requests"]) == 1 for c in p["chains"])
    assert p == plan("open_loop", "chat-steady", {"rate_per_s": 4.0}, seed)  # deterministic


def test_closed_loop_has_one_chain_a_client_and_balanced_rounds():
    p = plan("closed_loop", "chat-saturate", {"clients": 64}, 5)
    q = plan("closed_loop", "chat-saturate", {"clients": 64}, 6)
    assert len(p["chains"]) == 64
    first_round = sorted(c["requests"][0]["max_tokens"] for c in p["chains"])
    third_round = sorted(c["requests"][2]["max_tokens"] for c in p["chains"])
    assert first_round == third_round  # every round is the same sample
    assert all(c["start_s"] < p["lead_in_s"] for c in p["chains"])
    # Another seed is the same schedule with other words (PERF.md, PR 26).
    assert shape(p) == shape(q)


def test_sessions_replay_the_same_script_for_every_seed():
    p = plan("sessions", "sessions-prefix", {"rate_per_s": 0.5}, 5, seconds=50.0)
    q = plan("sessions", "sessions-prefix", {"rate_per_s": 0.5}, 2**31 + 6, seconds=50.0)
    assert shape(p) == shape(q)


def test_seats_hold_one_chain_a_user_that_outlasts_a_faster_server():
    traffic = load("traffic", "sessions-prefix")
    p = plan("sessions", "sessions-prefix", {"seats": 10}, 5, seconds=51.0)
    assert len(p["chains"]) == 10 and shape(p) == shape(
        plan("sessions", "sessions-prefix", {"seats": 10}, 6, seconds=51.0)
    )
    horizon = p["lead_in_s"] + 51.0
    for c in p["chains"]:
        assert c["start_s"] < p["lead_in_s"]
        # Even if every reply were instant, the think times alone fill the run.
        assert len(c["requests"]) * traffic["think_s_mean"] >= horizon
        assert all(r["messages"][0]["role"] == "system" for r in c["requests"])


def test_sessions_share_a_tenant_prompt_and_grow_their_history():
    p = plan("sessions", "sessions-prefix", {"rate_per_s": 0.5}, 5, seconds=50.0)
    traffic = load("traffic", "sessions-prefix")
    systems = {c["requests"][0]["messages"][0]["content"] for c in p["chains"]}
    assert len(systems) == 1  # FakeText: all tenants render alike; the role is what matters
    under_way = [c for c in p["chains"] if len(c["requests"][0]["messages"]) > 2]
    assert under_way and all(c["start_s"] < p["lead_in_s"] for c in under_way)
    for c in p["chains"]:
        assert 1 <= len(c["requests"]) <= traffic["turns"]["max"]
        sizes = [len(r["messages"]) for r in c["requests"]]
        assert all(b - a == 2 for a, b in zip(sizes, sizes[1:]))  # one turn more each time
        assert all(r["messages"][0]["role"] == "system" for r in c["requests"])
        assert c["requests"][0]["gap_s"] == 0.0


def test_text_maker_hits_the_wanted_length(tmp_path):
    from dynamo_exp_tpu.models.synth import write_full_vocab_tokenizer
    from tokenizers import Tokenizer

    write_full_vocab_tokenizer(str(tmp_path), 2048, seed=0)
    f = os.path.join(str(tmp_path), "tokenizer.json")
    tm = textgen.TextMaker(f, random.Random(3), words=256)
    tok = Tokenizer.from_file(f)
    for n in (16, 160, 1024):
        got = len(tok.encode(tm.text(n), add_special_tokens=False).ids)
        assert abs(got - n) <= max(2, n // 50)


def test_the_served_vocabulary_is_printable_and_whole(tmp_path):
    """Every id decodes, and all but the bare byte symbols decode to
    printable text, so the server's detokenizer has nothing to withhold."""
    from tokenizers import Tokenizer

    from lib import modeldir

    hf = {"vocab_size": 4096, "model_type": "qwen3"}
    path = modeldir.prepare(str(tmp_path / "model"), hf)
    tok = Tokenizer.from_file(os.path.join(path, "tokenizer.json"))
    assert tok.get_vocab_size() == 4096
    merged = range(5 + 256, 4096)
    texts = [tok.decode([i], skip_special_tokens=True) for i in merged]
    assert all(t and t.isprintable() and t.isascii() for t in texts)
    with open(os.path.join(path, "config.json")) as f:
        assert json.load(f) == hf
    stamp = os.path.getmtime(os.path.join(path, "tokenizer.json"))
    assert modeldir.prepare(path, hf) == path  # reused, not rewritten
    assert os.path.getmtime(os.path.join(path, "tokenizer.json")) == stamp
