"""Entry ``engine``: one ``ServingEngine`` driven open loop through its
public API (``submit`` / ``step``).

The engine is built, warmed and wrapped as the fleet entry's engines
are. Requests come from ``traffic.engine_requests``: a burst due at the
start of the pre-roll, then Poisson arrivals, each submitted once due;
the engine's own queue holds what waits for a slot. The window is
``[preroll_s, preroll_s + seconds)`` from the first submit. When it
closes, every request still in the engine's queue is withdrawn
(``cancel``) and logged as ``queued_at_close``: nothing is admitted
after the close, and those requests are neither attempted nor failed.
The requests admitted before the close are ``attempted``; ``failed`` are
those of them unfinished at the drain limit, finished without a token,
or finished short of their ``max_new_tokens`` with no end-of-sequence
token and room left in the cache. A single engine has no recovery path:
a device error ends the run.
"""
from __future__ import annotations

import threading
import time

from chipbench import drive_common as C
from chipbench import traffic, weights
from chipbench.recorder import Recorder


def shapes(cell) -> list:
    """The engine's shape for ``rehearse.py``: (label, slots, max_len,
    chunk, replicas, g, kv_width), where the widest prefill is every slot
    at once (the burst) against the lines of the longest prompt, on the
    engine's power-of-two ladder from 8."""
    d = cell.cell["deployment"]
    slots, max_len = int(d["slots"]), int(d["max_len"])
    kv = 8
    while kv < int(cell.traffic["prompt"]["max"]):
        kv *= 2
    return [("engine", slots, max_len, int(d["prefill_chunk"]), 1, slots,
             min(kv, max_len))]


def _finished_short(req, ctx_len: int, max_len: int, eos: int) -> bool:
    """Finished, yet by none of the engine's rules for finishing."""
    n = len(req.output_ids)
    return (n < req.max_new_tokens and req.output_ids[-1] != eos
            and ctx_len + n < max_len - 1)


def _longest(xs: list, n: int = 3) -> list:
    return [round(1000.0 * x, 3) for x in sorted(xs, reverse=True)[:n]]


def run(ctx) -> dict:
    from repro.data.tokenizer import EOS_ID

    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    mix, dep, model = cell.traffic, cell.cell["deployment"], cell.model
    fam = cell.family
    cfg = fam.served_config(model)
    sizes = fam.sizes(model)
    max_len = int(dep["max_len"])
    words = [weights.seed_words(seed, 0)]
    engine = C.build_engine(cfg, fam.program_params(cfg, sizes, words[0]),
                            dep)
    ctx.mark("engines")
    rec = Recorder()
    log = rec.wrap_engine(engine, "engine")

    reqs = traffic.engine_requests(mix, seconds)
    ids = traffic.prompt_ids(reqs, seed, sizes.vocab)
    ctx.note(f"requests {len(reqs)} digest {traffic.digest(reqs)} "
             f"ids {traffic.digest(map(tuple, ids))}")
    # the burst is admitted together: warm its own prefill shapes too
    burst = [r.prompt for r in reqs[:int(mix.get("burst", 0))]]
    plan = list(cell.cell.get("warm", [])) + (
        [{"lengths": burst}] if burst else [])
    steps = C.warm_engine(engine, plan, sizes.vocab, seed)
    ctx.mark("warm", warm_steps=steps)

    lo_rel, hi_rel = traffic.window_of(mix, seconds)
    start = len(log.tracked)
    t0 = time.perf_counter()
    w0, w1 = t0 + lo_rel, t0 + hi_rel
    ctx.open_window(w0, w1, [engine])
    deadline = w1 + float(mix.get("drain_s", 120.0))
    done = threading.Event()
    ctx.watchdog(done, deadline + C.HANG_S)
    submitted, queued, late, i, closed = [], [], 0.0, 0, False
    while True:
        now = time.perf_counter()
        if not closed and now >= w1:
            closed = True
            waiting = {id(q) for q in engine.queue}
            queued = [r for r in submitted if id(r) in waiting]
            for r in queued:
                engine.cancel(r)
        while not closed and i < len(reqs) and t0 + reqs[i].due <= now:
            late = max(late, now - t0 - reqs[i].due)
            submitted.append(engine.submit(ids[i],
                                           max_new_tokens=reqs[i].answer))
            i += 1
        if now > deadline:
            break
        if engine.has_work:
            engine.step()
        elif closed:
            break
        else:
            nxt = min(t0 + reqs[i].due if i < len(reqs) else w1, w1)
            time.sleep(max(0.0, nxt - time.perf_counter()))
    done.set()
    ctx.close_window([engine])
    ctx.stop_trace()
    t_end = time.perf_counter()
    steps = [s for s in log.steps if w0 <= s.t1 <= w1]

    gone = {id(r) for r in queued}
    admitted = [tr for tr in log.tracked[start:] if id(tr.req) not in gone]
    unfinished = [tr for tr in admitted
                  if not (tr.req.done and tr.req.output_ids)]
    short = [tr for tr in admitted
             if tr.req.done and tr.req.output_ids and _finished_short(
                 tr.req, len(traffic.clip_prompt(
                     tr.req.prompt_ids, max_len, tr.req.max_new_tokens,
                     sizes.vocab)), max_len, EOS_ID)]
    failed = len(unfinished) + len(short)
    ctx.check.require(bool(admitted), "no request admitted")
    ctx.check.require(not unfinished, f"{len(unfinished)} of the "
                      f"{len(admitted)} admitted requests unfinished at the "
                      f"drain limit or without a token")
    ctx.check.require(not short, f"{len(short)} requests finished short of "
                      f"max_new_tokens with no end-of-sequence token")
    result = {
        "recorder": rec, "window": (w0, w1), "attempted": len(admitted),
        "failed": failed,
        "counters": {"engine": {k: v for k, v in engine.stats.items()
                                if isinstance(v, (int, float))}},
        "drain_s": t_end - w1, "sizes": sizes,
        "detail": {"submitted": len(submitted),
                   "queued_at_close": len(queued),
                   "finished": sum(tr.req.done for tr in admitted),
                   "tokens_out": sum(len(tr.req.output_ids)
                                     for tr in admitted),
                   "ended_early": sum(
                       tr.req.done and len(tr.req.output_ids) <
                       tr.req.max_new_tokens for tr in admitted),
                   "generator_late_max_s": round(late, 4),
                   # where a window lost time: its longest steps and the
                   # longest host gaps between two steps, in ms
                   "longest_steps_ms": _longest(
                       [s.t1 - s.t0 for s in steps]),
                   "longest_gaps_ms": _longest(
                       [b.t0 - a.t1 for a, b in zip(steps, steps[1:])])},
    }
    ctx.memory_peak = C.peak_bytes()
    corr = cell.cell["correct"]
    served = C.served_of(admitted, max_len, sizes.vocab, engine="engine")
    pick = C.sample(served, seed, int(corr["sample_tokens"]),
                    int(corr["max_requests"]))
    rec.release()
    del engine, log, submitted, queued, admitted, unfinished, short
    C.free_device()
    result["reference"] = C.check_served(ctx.check, fam, model, words, pick,
                                         float(corr["limit"]),
                                         controls=ctx.controls)
    return result
