#!/usr/bin/env python3
"""What a cached asking's numbers depend on: the fixed greedy probes asked
again and again in ONE process, through the states a cell's run passes
(fresh, after another request took page 0; after the oldest parked pages
were evicted; after all of them were), with a record of what each asking READ and
RAN, so that two askings that differ are traced to the first quantity
that does.

    python3 benchmark/rehearse/probe_states.py --workload mixtral-8x7b.chat-saturate \\
        --tag states --seed 2147484311 --askings 3 --evict-pages 10,2600

For every asking of every probe, ``chiprun_out/bench/<tag>.json`` holds:

- ``text`` (its hash) and ``usage``;
- ``alloc``: the pages the admission bound and how many tokens it took
  from them (read off ``KvPageManager.allocate_sequence``'s result), and,
  where the program's flight recorder has it, each page's ``how`` and
  ``writer`` (``engine.py::_attached_pages``);
- ``page_crc``: a CRC of the raw bits of K and of V in every slot the
  asking may read (positions below ``cached_len``), a page at a time,
  taken from the pools at the asking's first dispatch;
- ``dispatches``: the variant key of every ragged dispatch it ran
  (``_ragged_fn_from_key``'s argument: bucket, page bound, windowed,
  sampler, log-probabilities, speculation);
- ``replay``: the asking's first dispatch run again on its captured
  inputs, outside the engine loop, through ``models/llama.py::
  forward_ragged`` with the engine's attention form and mesh; of the
  logits at the first sampled position, a CRC, the best token and its
  margin. Four ways: as captured, twice (does the program repeat?); with
  every K and V of page 0 scaled by 100 (memory the asking does not own;
  page 0 is what clamped and zero table entries read); and with the
  padding slots' token ids changed (the padding rows' own inputs). A
  sound program gives one CRC four times.

``verdict`` compares, a probe at a time, every cached asking (all but one
prompt token from pages) with every other: same text, and if not, the
first of pages, page bits, dispatch keys and replayed logits that
differs. Nothing here is a timing.

The hooks are two attributes set on the engine object the server built
(``_ragged_fn_from_key`` and ``kv.allocate_sequence`` wrapped in place),
so the same file runs against a checkout that lacks this PR's record.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "bench")

import run_cell  # noqa: E402

LOAD_PROMPT_TOKENS = 500  # one mixed dispatch a request, at the 512 bucket


class Tap:
    """What the engine's loop thread allocates and dispatches while an
    asking is in flight (one request at a time: the engine is idle
    otherwise)."""

    def __init__(self, engine):
        self.engine = engine
        self.reset()
        plain_alloc = engine.kv.allocate_sequence
        plain_fn = engine._ragged_fn_from_key

        def allocate_sequence(tokens, max_pages, request_id=""):
            alloc = plain_alloc(tokens, max_pages, request_id)
            if alloc is not None and self.armed:
                self.alloc = {
                    "page_ids": list(alloc.page_ids), "cached_len": alloc.cached_len,
                    "cached_pages": alloc.cached_pages,
                    "shared_tail": list(alloc.shared_tail) if alloc.shared_tail else None,
                    "prompt_tokens": len(tokens),
                }
            return alloc

        def ragged_fn_from_key(key):
            fn = plain_fn(key)

            def call(*args):
                if self.armed:
                    self.keys.append(list(key))
                    if self.first is None and not key[2]:  # the mixed dispatch
                        self.first = self._capture(key, args)
                return fn(*args)

            return call

        engine.kv.allocate_sequence = allocate_sequence
        engine._ragged_fn_from_key = ragged_fn_from_key

    def reset(self):
        self.armed, self.alloc, self.keys, self.first = False, None, [], None

    def _capture(self, key, args) -> dict:
        """Host copies of the dispatch's small inputs and CRCs of the
        pages it may read, before the program overwrites the pools."""
        import numpy as np

        k, v = args[1], args[2]
        names = ("tokens", "positions", "row_of", "table", "q_last")
        small = {n: np.asarray(a) for n, a in zip(names, args[3:8])}
        crc = []
        if self.alloc is not None:
            ps = k.shape[2]
            pids = self.alloc["page_ids"]
            readable = self.alloc["cached_len"]
            n_pages = -(-readable // ps)
            if n_pages:
                idx = np.asarray(pids[:n_pages], np.int32)
                kk = np.asarray(k[:, idx]).view(np.uint16)  # [L, n, ps, W]
                vv = np.asarray(v[:, idx]).view(np.uint16)
                for j, pid in enumerate(pids[:n_pages]):
                    upto = min(ps, readable - j * ps)
                    crc.append([
                        int(pid), upto,
                        zlib.crc32(np.ascontiguousarray(kk[:, j, :upto]).tobytes()),
                        zlib.crc32(np.ascontiguousarray(vv[:, j, :upto]).tobytes()),
                    ])
        return {"key": list(key), "small": small, "page_crc": crc}


def make_replay(engine, key):
    """``run(tokens, positions, row_of, table, out_idx, page0_scale)``:
    the mixed program's forward on the engine's live pools (not
    donated), page 0 scaled on the way in; the logits at ``out_idx``."""
    import jax
    import jax.numpy as jnp

    from dynamo_exp_tpu.aot.lattice import impl_for_key
    from dynamo_exp_tpu.models import llama

    nb, pages, *_ = key
    mcfg, mesh = engine.cfg.model, engine.mesh
    impl, interpret, q_tile = impl_for_key(tuple(key)), engine._attn_interpret, engine._ragged_align()

    @jax.jit
    def run(params, k, v, tokens, positions, row_of, table, out_idx, page0_scale):
        k = k.at[:, 0].multiply(page0_scale.astype(k.dtype))
        v = v.at[:, 0].multiply(page0_scale.astype(v.dtype))
        logits, _k, _v = llama.forward_ragged(
            params, mcfg, tokens, positions, row_of, table, k, v, out_idx,
            attn_pages=pages, attn_impl=impl, q_tile=q_tile, mesh=mesh, interpret=interpret,
        )
        return logits

    return run


class States(run_cell.Supervisor):
    def _ask(self, tap: Tap, label: str, content: str, n_tokens: int, replays: dict) -> dict:
        import jax.numpy as jnp
        import numpy as np
        from lib import correct

        tap.reset()
        tap.armed = True
        status, resp = correct.ask_greedy(self.base, run_cell.MODEL_NAME, content, n_tokens)
        tap.armed = False
        time.sleep(0.2)
        text = resp["choices"][0]["message"].get("content") if status == 200 else str(resp)[:200]
        rec = {
            "label": label, "status": status,
            "text": hashlib.sha1((text or "").encode()).hexdigest()[:8], "bytes": len(text or ""),
            "usage": resp.get("usage") if status == 200 else None,
            "alloc": tap.alloc, "dispatches": tap.keys,
        }
        first = tap.first
        if first is None:
            return rec
        rec["page_crc"] = first["page_crc"]
        if self.engine.flight is not None:
            admits = [e for e in self.engine.flight.snapshot() if e["kind"] == "admit"]
            if admits and "pages" in admits[-1]:
                rec["attached"] = admits[-1]["pages"]
        # Replay the first dispatch on the pools as they are now: the slots
        # below cached_len are as the asking read them (nothing has been
        # admitted since; the asking's own writes lie at or past cached_len).
        key = tuple(first["key"])
        s = first["small"]
        if key not in replays:
            replays[key] = make_replay(self.engine, key)
        run = replays[key]
        live = int(np.argmax(s["positions"]))  # the prompt's last token
        pad_alt = np.where(s["positions"] < 0, 7, s["tokens"]).astype(s["tokens"].dtype)
        out = {}
        for name, toks, scale in (
            ("as_read", s["tokens"], 1.0), ("again", s["tokens"], 1.0),
            ("page0_x100", s["tokens"], 100.0), ("pad_ids_7", pad_alt, 1.0),
        ):
            logits = np.asarray(run(
                self.engine.params, self.engine.k_cache, self.engine.v_cache,
                jnp.asarray(toks), jnp.asarray(s["positions"]), jnp.asarray(s["row_of"]),
                jnp.asarray(s["table"]), jnp.asarray([live], jnp.int32),
                jnp.asarray(scale, jnp.float32),
            ))[0]
            top = np.argsort(-logits)[:2]
            out[name] = {
                "crc": zlib.crc32(logits.tobytes()), "best": int(top[0]),
                "margin": float(logits[top[0]] - logits[top[1]]),
            }
        rec["replay"] = out
        if 0 in (tap.alloc or {}).get("page_ids", [0]):
            # Page 0 is this asking's own: scaling it is no test of foreign memory.
            rec["owns_page0"] = True
            out.pop("page0_x100")
        rec["pad_slots"] = int((s["positions"] < 0).sum())
        return rec

    def _load(self, text, evict: int) -> dict:
        """Admit prompts no one shares until the free list is empty and
        the LRU has given up its ``evict`` oldest parked pages (a finished
        sequence parks its pages in page order, so a chain goes root
        first and its later pages stay registered but unreachable)."""
        from lib import correct

        kv = self.engine.kv
        ps = kv.page_size
        before = (len(kv._free), len(kv._reclaimable))
        evicted, plain_evict = [0], kv._evict

        def counting(pid):
            evicted[0] += 1
            return plain_evict(pid)

        kv._evict = counting
        n = 0
        try:
            while evicted[0] < evict:
                # Whole prompts of LOAD_PROMPT_TOKENS, then one that takes the
                # rest (a finished prompt gives its partial last page back).
                left = len(kv._free) + evict - evicted[0]
                tokens = min(LOAD_PROMPT_TOKENS, left * ps - ps // 2)
                status, resp = correct.http_json(
                    self.base + "/v1/chat/completions",
                    {"model": run_cell.MODEL_NAME, "messages": text.user_message(max(tokens, ps)),
                     "max_tokens": 1, "temperature": 0, "ignore_eos": True, "stream": False},
                )
                if status != 200:
                    raise run_cell.BenchFailure(f"load request: HTTP {status}: {str(resp)[:200]}")
                n += 1
        finally:
            kv._evict = plain_evict
        return {"requests": n, "evicted": evicted[0], "free_parked_before": before,
                "free_parked_after": (len(kv._free), len(kv._reclaimable))}

    def _run(self) -> dict:
        from lib import correct
        from lib.textgen import TextMaker
        from tokenizers import Tokenizer

        args = self.args
        self._wait_ready()
        run_cell.say(f"server ready after {time.monotonic() - run_cell.T_START:.1f}s")
        tap = Tap(self.engine)
        tok_file = os.path.join(self.model_dir, "tokenizer.json")
        text = TextMaker(tok_file, random.Random(args.seed))
        result = {"workload": self.spec["name"], "device": self.device, "seed": args.seed, "phases": []}
        replays: dict = {}

        def flush():
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{args.tag}.json"), "w") as f:
                json.dump(result, f, indent=1)

        # The process's first request takes page 0, as the comparison's first
        # probe does in a cell's run: seeded text, so page 0 turns with --seed.
        first, _ids = correct._probe_prompts(Tokenizer.from_file(tok_file), args.seed, 1)[0]
        result["first_request"] = self._ask(tap, "first", first, correct.PROBE_DECODE_TOKENS, replays)

        def askings(phase: str):
            recs = []
            for k in range(args.askings):
                for i, (content, n) in enumerate(correct.FIXED_PROBES):
                    r = self._ask(tap, f"{phase}/{k}/{i}", content, n, replays)
                    r.update(phase=phase, asking=k, probe=i)
                    recs.append(r)
                    run_cell.say(json.dumps({
                        k2: r.get(k2) for k2 in ("label", "text", "bytes", "dispatches")
                    } | {"cached": (r["alloc"] or {}).get("cached_len"),
                         "replay": {n2: v["crc"] for n2, v in (r.get("replay") or {}).items()}}))
            result["phases"].append({"phase": phase, "askings": recs})
            flush()

        askings("fresh")
        for j, pages in enumerate(args.evict_pages):
            load = self._load(text, pages)
            run_cell.say(f"load {j}: {load}")
            result.setdefault("loads", []).append(load)
            askings(f"after_load_{j}")
        result["verdict"] = verdict(result)
        flush()
        return result


def verdict(result: dict) -> dict:
    """A probe at a time: do all cached askings agree, and where not,
    what differs first between the first cached asking and each other."""
    out = {}
    by_probe: dict[int, list] = {}
    for ph in result["phases"]:
        for r in ph["askings"]:
            a = r.get("alloc") or {}
            if r["status"] == 200 and a and a["cached_len"] == a["prompt_tokens"] - 1:
                by_probe.setdefault(r["probe"], []).append(r)
    for probe, recs in sorted(by_probe.items()):
        base, diffs = recs[0], []
        for r in recs[1:]:
            what = []
            if r["text"] != base["text"]:
                what.append("text")
            if r["alloc"]["page_ids"][: len(r.get("page_crc", []))] != base["alloc"]["page_ids"][: len(base.get("page_crc", []))]:
                what.append("pages")
            if [c[1:] for c in r.get("page_crc", [])] != [c[1:] for c in base.get("page_crc", [])]:
                bad = [
                    i for i, (x, y) in enumerate(zip(r.get("page_crc", []), base.get("page_crc", [])))
                    if x[1:] != y[1:]
                ]
                what.append(f"page_bits@{bad}")
            if r["dispatches"] != base["dispatches"]:
                what.append("dispatch_keys")
            if (r.get("replay") or {}).get("as_read", {}).get("crc") != (base.get("replay") or {}).get("as_read", {}).get("crc"):
                what.append("replayed_logits")
            if what:
                diffs.append({"label": r["label"], "against": base["label"], "differs": what})
        sound = all(
            len({v["crc"] for v in (r.get("replay") or {"x": {"crc": 0}}).values()}) == 1
            for r in recs
        )
        out[str(probe)] = {
            "cached_askings": len(recs), "texts": sorted({r["text"] for r in recs}),
            "replay_four_ways_agree": sound, "differences": diffs,
        }
    return out


def ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seed", type=int, default=2147484311)
    ap.add_argument("--askings", type=int, default=3)
    ap.add_argument("--evict-pages", type=ints, default=[],
                    help="after each round of askings, load until this many parked pages were evicted")
    ap.add_argument("--out", default=OUT, help="directory of <tag>.json")
    args = ap.parse_args()
    args.trace, args.seconds = 0, 0.0
    sup, server_error = run_cell.serve(run_cell.load_cell(args.workload), args, States, warm=False)
    if server_error or sup.error or sup.result is None:
        run_cell.say(f"no result: server={server_error!r} supervisor={sup.error!r}")
        return 1
    print(json.dumps(sup.result["verdict"]))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
