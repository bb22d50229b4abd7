"""What every entry shares: engines, warm-up through the public engine
API, and the check of served tokens against the reference of the
configuration's family (``families/<family>.py``)."""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.recorder import Tracked

# past the drain limit, the seconds a device step may still take before
# the process is ended as hung
HANG_S = 60.0


def build_engine(cfg, params, dep: dict, slots: Optional[int] = None):
    from repro.serving.engine import ServingEngine
    return ServingEngine(cfg, params,
                         batch_slots=int(slots or dep["slots"]),
                         max_len=int(dep["max_len"]),
                         dtype=jnp.dtype(cfg.dtype),
                         prefill_chunk=int(dep["prefill_chunk"]),
                         prefix_reuse=bool(dep.get("prefix_reuse", True)))


def warm_engine(engine, plan: List[dict], vocab: int, seed: int) -> int:
    """Run the warm-up passes of a cell file through the public engine
    API and return the steps taken. A pass admits requests together and
    steps until they finish; ``{"g": [a, b], "prompt": [n, ...]}`` is one
    pass of ``g`` requests of length ``n`` for each ``g`` and ``n``.
    ``{"prefix": [n, ...], "length": L}`` serves a request of ``L``
    tokens in the last slot and then, for each ``m`` below the slots,
    ``m`` requests that share its first ``n`` tokens, so that the
    prefix-seed copy runs for ``m`` slots at every width the prefix hits
    reach. ``{"lengths": [n, ...]}`` is one pass that admits a request
    of each length together. Other ids are random, so no other pass
    shares a prefix."""
    rng = traffic.rng_for(seed, 99)
    passes = []
    for p in plan:
        if "prefix" in p:
            n_all, last = int(p["length"]), engine.slots - 1
            for n in p["prefix"]:
                for m in range(1, engine.slots):
                    # the shared request takes the last slot, so that the
                    # m requests after it lease other slots and copy
                    head = rng.integers(16, vocab, n_all).tolist()
                    passes.append(([n_all] * last + [head], p.get("new", 2)))
                    passes.append((
                        [head[:int(n)] + rng.integers(16, vocab, n_all - int(n)
                                                      ).tolist()
                         for _ in range(m)], p.get("new", 2)))
            continue
        if "lengths" in p:
            passes.append(([int(n) for n in p["lengths"]], p.get("new", 2)))
            continue
        g0, g1 = p["g"]
        for g in range(int(g0), min(int(g1), engine.slots) + 1):
            passes += [([int(n)] * g, p.get("new", 2)) for n in p["prompt"]]
    steps = 0
    for prompts, new in passes:
        for ids in prompts[:engine.slots]:
            if isinstance(ids, int):
                ids = rng.integers(16, vocab, ids).tolist()
            engine.submit(ids, max_new_tokens=int(new))
        while engine.has_work:
            engine.step()
            steps += 1
    return steps


def peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def free_device() -> None:
    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---- correctness -------------------------------------------------------

@dataclass
class Served:
    """One finished request as the check sees it."""

    ctx: List[int]
    out: List[int]
    stream: int = 0           # which weight stream served it
    engine: str = ""          # the engine (or pool replica) that served it


def sample(served: List[Served], seed: int, tokens: int,
           max_requests: int) -> List[Served]:
    """A seeded sample of finished requests with the longest in it: add
    requests in random order until ``tokens`` served tokens are in."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: (len(served[i].out), len(served[i].ctx)))
    order = traffic.rng_for(seed, 7).permutation(len(served))
    pick, n = [longest], len(served[longest].out)
    for i in order:
        if n >= tokens or len(pick) >= max_requests:
            break
        if int(i) != longest:
            pick.append(int(i))
            n += len(served[int(i)].out)
    return [served[i] for i in pick]


def served_of(tracked: List[Tracked], max_len: int, vocab: int,
              stream: int = 0, engine: str = "") -> List[Served]:
    """Finished requests as the check sees them."""
    out = []
    for tr in tracked:
        r = tr.req
        if r.done and r.output_ids:
            ctx = traffic.clip_prompt(r.prompt_ids, max_len,
                                      r.max_new_tokens, vocab)
            out.append(Served(ctx, [int(x) for x in r.output_ids], stream,
                              engine))
    return out


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def logit_gaps(family, sizes, w: dict, reqs: List[Served],
               modes=("f32",)) -> Dict[str, List[float]]:
    """Per request and mode, the widest gap by which a judged token's
    logit lies below the reference's best. In mode ``f32`` the judged
    tokens are the served ones; in a control mode they are the ones that
    the lower precision puts first at the same positions."""
    out: Dict[str, List[float]] = {m: [] for m in modes}
    for r in reqs:
        seq = r.ctx + r.out[:-1]
        T = _bucket(len(seq), family.Q_BLOCK)
        P = _bucket(len(r.out), 64)
        ids = np.zeros(T, np.int32)
        ids[:len(seq)] = seq
        want = np.zeros(P, np.int32)
        want[:len(r.out)] = len(r.ctx) - 1 + np.arange(len(r.out))
        ids, want = jnp.asarray(ids), jnp.asarray(want)
        ref = family.logits_at(sizes, w, ids, want, "f32")
        best = jnp.max(ref, -1)
        for mode in modes:
            if mode == "f32":
                tok = jnp.asarray(np.pad(r.out, (0, P - len(r.out))))
            else:
                tok = jnp.argmax(family.logits_at(sizes, w, ids, want,
                                                  mode), -1)
            at = jnp.take_along_axis(ref, tok[:, None].astype(jnp.int32),
                                     -1)[:, 0]
            out[mode].append(float(np.asarray(best - at)[:len(r.out)].max()))
        del ref
    return out


@dataclass
class Check:
    """Numbers compared against their limits, printed by ``run.py``."""

    numbers: Dict[str, List[float]] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)
    ok: bool = True

    def compare(self, name: str, value: float, limit: float) -> None:
        """A number that must not exceed its limit."""
        self.numbers[name] = [value, limit]
        self.ok = self.ok and bool(value <= limit and np.isfinite(value))

    def require(self, cond: bool, note: str) -> None:
        if not cond:
            self.ok = False
            self.notes.append(note)


def check_served(check: Check, family, model: dict, seeds: List,
                 reqs: List[Served], limit: float,
                 controls: tuple = ()) -> Dict[str, object]:
    """Draw each weight stream again and compare its sampled requests
    with the family's reference; each precision in ``controls`` is read
    too (the limit-setting tool asks for them, benchmark runs do not)."""
    sizes = family.sizes(model)
    t0 = time.perf_counter()
    gaps: Dict[str, List[float]] = {m: [] for m in ("f32",) + tuple(controls)}
    for stream, words in enumerate(seeds):
        mine = [r for r in reqs if r.stream == stream]
        if not mine:
            continue
        w = family.canonical(sizes, words, jnp.dtype(model["torch_dtype"]))
        for mode, v in logit_gaps(family, sizes, w, mine,
                                  tuple(gaps)).items():
            gaps[mode].extend(v)
        del w
        free_device()
    check.require(bool(gaps["f32"]), "no finished request to compare")
    judged = {}
    for mode, v in gaps.items():
        # each control, in the program's place, is judged as a run is
        c = check if mode == "f32" else Check()
        c.compare("max_logit_gap", max(v) if v else float("inf"), limit)
        judged[mode] = {"max_logit_gap": c.numbers["max_logit_gap"][0],
                        "correct": c.ok}
    return {"requests": len(reqs),
            "served_tokens": sum(len(r.out) for r in reqs),
            "gaps": gaps["f32"],
            "controls": {m: j for m, j in judged.items() if m != "f32"},
            "reference_s": time.perf_counter() - t0}
