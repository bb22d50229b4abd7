"""What every entry shares: the served model from a config file, its
weights from the seed, engines, warm-up through the public engine API,
and the check of served tokens against the reference."""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, traffic, weights
from chipbench.recorder import Tracked

# ModelConfig fields that must equal what the config file states
_STATED = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
           "d_ff": "intermediate_size", "vocab_size": "vocab_size",
           "qkv_bias": "qkv_bias", "tie_embeddings": "tie_word_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "dtype": "torch_dtype"}


def model_config(model: dict):
    """The program's ``ModelConfig`` for a config file, checked against
    every size the file states."""
    from repro.configs import get_config
    cfg = get_config(model["arch_id"]).variant(**model.get("overrides", {}))
    for f, key in _STATED.items():
        got, want = getattr(cfg, f), model[key]
        if got != want:
            raise ValueError(f"{model['arch_id']}: the program's {f}={got!r} "
                             f"departs from the stated {key}={want!r}")
    if cfg.family != "dense" or cfg.decode_window() is not None:
        raise ValueError(f"{model['arch_id']}: served config is not a "
                         f"full-attention dense decoder as stated")
    return cfg


def program_params(cfg, sizes: weights.Sizes, words):
    """The seeded canonical weights, laid out as the program's params
    pytree (checked against ``init_params``'s shapes)."""
    from repro.models import model as M
    tmpl = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    w = weights.canonical(sizes, words, jnp.dtype(cfg.dtype))
    vp = tmpl["embed"]["w"].shape[0]

    def rows(x):
        return jnp.pad(x, ((0, vp - x.shape[0]), (0, 0)))

    def lin(name):
        d = {"w": w[name]}
        b = "b" + name[1]
        if name in ("wq", "wk", "wv") and b in w:
            d["b"] = w[b]
        return d

    p = {"embed": {"w": rows(w["embed"])},
         "layers": {"ln1": {"scale": w["ln1"]},
                    "attn": {n: lin(n) for n in ("wq", "wk", "wv", "wo")},
                    "ln2": {"scale": w["ln2"]},
                    "mlp": {n: {"w": w[n]}
                            for n in ("w_gate", "w_up", "w_down")}},
         "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        p["lm_head"] = {"w": rows(w["lm_head"])}
    want = jax.tree.map(lambda s: (s.shape, s.dtype), tmpl)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), p)
    if want != got:
        raise ValueError(f"weights do not match the program's parameter "
                         f"layout for {cfg.arch_id}")
    del w
    return p


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
    reach. Other ids are random, so no other pass shares a prefix."""
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


def logit_gaps(sizes: weights.Sizes, w: dict, reqs: List[Served],
               modes=("f32",)) -> Dict[str, List[float]]:
    """Per request and mode, the widest gap by which a judged token's
    logit lies below the reference's best. In mode ``f32`` the judged
    tokens are the served ones; in a control mode they are the ones that
    the lower precision puts first at the same positions."""
    out: Dict[str, List[float]] = {m: [] for m in modes}
    for r in reqs:
        seq = r.ctx + r.out[:-1]
        T = _bucket(len(seq), reference.Q_BLOCK)
        P = _bucket(len(r.out), 64)
        ids = np.zeros(T, np.int32)
        ids[:len(seq)] = seq
        want = np.zeros(P, np.int32)
        want[:len(r.out)] = len(r.ctx) - 1 + np.arange(len(r.out))
        ids, want = jnp.asarray(ids), jnp.asarray(want)
        ref = reference.logits_at(sizes, w, ids, want, "f32")
        best = jnp.max(ref, -1)
        for mode in modes:
            if mode == "f32":
                tok = jnp.asarray(np.pad(r.out, (0, P - len(r.out))))
            else:
                tok = jnp.argmax(reference.logits_at(sizes, w, ids, want,
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


def check_served(check: Check, model: dict, seeds: List,
                 reqs: List[Served], limit: float,
                 controls: tuple = ()) -> Dict[str, object]:
    """Draw each weight stream again and compare its sampled requests
    with the reference; each precision in ``controls`` is read too (the
    limit-setting tool asks for them, benchmark runs do not)."""
    sizes = weights.Sizes.of(model)
    t0 = time.perf_counter()
    gaps: Dict[str, List[float]] = {m: [] for m in ("f32",) + tuple(controls)}
    for stream, words in enumerate(seeds):
        mine = [r for r in reqs if r.stream == stream]
        if not mine:
            continue
        w = weights.canonical(sizes, words, jnp.dtype(model["torch_dtype"]))
        for mode, v in logit_gaps(sizes, w, mine, tuple(gaps)).items():
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
