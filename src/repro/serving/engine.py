"""Serving engine: batched chunked prefill + batched decode over any repro
model.

The engine serves fixed-size micro-batches with a KV cache pool:
``submit`` enqueues requests, ``step`` admits waiting requests into free
slots (continuous batching), prefills them, and advances every active
request by one decode token.

Hot path (dense decoders — the HybridFlow edge/cloud executor archs):

* **Batched chunked prefill** — a prefill planner drains all newly
  admitted slots into ONE padded ``serve_prefill_chunk`` call per step;
  prompts longer than ``prefill_chunk`` are processed one chunk per step
  so long prompts never stall co-resident decodes. KV lines are written
  directly into the shared slot-pooled cache via ``dynamic_update_slice``
  — no per-request ``init_cache`` allocation, no whole-tree copy. Under
  ``REPRO_USE_PALLAS=1`` the chunk attention runs the ragged
  chunked-prefill Pallas kernel (``stats["prefill_backend"]`` records
  which backend served the last prefill call).
* **Cross-request KV prefix reuse** — completed prompts are indexed by
  chained block hashes (``kvcache.PREFIX_BLOCK`` tokens per block); a new
  lease that shares a cached block-aligned prefix seeds its slot with ONE
  batched cross-slot copy (or skips the copy entirely when it re-leases
  its own source slot) and prefills only the uncovered tail. Matches are
  verified token-exact, a free source slot is pinned against re-lease
  until the borrower's copy launches, and at least one tail token always
  prefills — greedy outputs are bit-identical to the no-reuse path.
  ``stats["prefix_hits"]``/``["prefill_tokens_saved"]`` report the win.
* **Device-side batched sampling** — greedy/temperature sampling for all
  live slots happens inside the jitted decode/prefill step (one PRNG key
  array, one [slots] host transfer of sampled ids per step) instead of a
  per-slot ``np.asarray(logits)`` round-trip.
* **Device-resident positions** — ``pos`` lives on device as int32 and is
  advanced inside the jitted step; inactive slots are parked at
  ``max_len - 1`` (a line no live request ever attends).

Configs that ``model.chunked_prefill_ok`` refuses (capacity-routed moe:
expert capacity couples batch rows; vlm / audio / hybrid / ssm: prefix or
recurrent state) fall back to the legacy per-slot batch-1 prefill, which
is kept as the reference path (``batched_prefill=False`` forces it for
any config). Dropless expert stacks (deepseek-v2-lite) take the batched
path, and their decode step also returns its expert load, accumulated in
``stats`` (``expert_picks_<i>``, ``experts_hit``).

Engine steps are split into a *launch* phase (host builds inputs and
issues the jitted call — JAX dispatch is async) and a *commit* phase
(the one host transfer + request bookkeeping). ``step`` runs both
back-to-back; ``repro.serving.pool.EnginePool`` launches every replica
before committing any, so one replica's host-side commit overlaps the
next replica's device compute.

``JAXExecutor`` adapts an engine — or an ``EnginePool`` of replicas —
to HybridFlow's Executor protocol so the paper's scheduler can drive
*real* JAX models. It exposes both the synchronous ``run`` and the async
``submit``/``poll``/``pump`` surface the fleet scheduler's pump loop
uses to overlap subtasks from different queries in the same
micro-batches (examples/serve_hybrid). When no explicit ``concurrency``
is given it derives from the backing engine's capacity (pool: replicas ×
slots), and ``saturated()`` reports live slot occupancy so the fleet's
cloud→edge spill only fires when every replica is really full.
"""
from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # the protocol lives in the package root (no cycle)
    from repro.serving import EngineLike

from repro.configs.base import ModelConfig
from repro.data import tokenizer as tok
from repro.models import kvcache as KV
from repro.models import model as M
from repro.tracing import span


@dataclass
class Request:
    rid: int
    prompt_ids: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    # filled by the engine:
    output_ids: List[int] = field(default_factory=list)
    done: bool = False

    @property
    def text(self) -> str:
        return tok.decode(self.output_ids)


@dataclass
class _PrefillJob:
    """Per-slot progress of an in-flight (possibly chunked) prefill."""

    ids: List[int]
    off: int = 0

    @property
    def remaining(self) -> int:
        return len(self.ids) - self.off


@dataclass
class _PrefillPass:
    """In-flight prefill launch awaiting its host commit."""

    jobs: List            # [(slot, _PrefillJob)] in slot order
    take: List[int]
    first: object         # device array of first sampled ids [G]


@dataclass
class _DecodePass:
    """In-flight decode launch awaiting its host commit."""

    live_slots: List[int]
    nxt: object           # device array of sampled ids [slots]
    load: object = None   # device array of expert load [held + 1], or None


def _device_sample(logits, key, temps):
    """Greedy/temperature sampling for all slots on device. logits [B,V].

    Greedy rows (``temperature == 0.0``, the default) still flow through
    the categorical branch before the ``where``-select, so the divisor
    must stay safe for them: ``where(temps > 0, temps, 1.0)`` keeps the
    scaled logits finite (a tiny-epsilon denominator amplifies the
    padded-vocab -1e9 logits toward the float32 edge and trips
    ``jax_debug_nans`` runs; dividing by exact 0 would be inf/NaN every
    step). The sampled value of a greedy row is discarded by the select.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = jax.random.split(key, logits.shape[0])
    safe_t = jnp.where(temps > 0, temps, 1.0)
    scaled = logits / safe_t[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


class _PrefixIndex:
    """Content-hashed index of the prompt prefixes currently held in the
    engine's KV slot pool.

    Prefixes are indexed at :data:`repro.models.kvcache.PREFIX_BLOCK`-token
    granularity with chained crc32 block hashes; a lookup walks the
    candidate boundaries longest-first and verifies the actual tokens
    before reporting a match, so hash collisions can never break the
    bit-identity contract. Entries are registered when a slot's prefill
    completes (its lines are then fully written and stable — decode only
    appends past the prompt) and evicted when the slot is re-leased (its
    lines are about to be overwritten from position 0).
    """

    def __init__(self, block: int):
        self.block = block
        self._slot_ids: Dict[int, tuple] = {}     # slot -> prompt token ids
        self._slot_hashes: Dict[int, tuple] = {}  # slot -> chained hashes
        self._by_hash: Dict[int, set] = {}        # chained hash -> slots

    def register(self, slot: int, ids) -> None:
        self.evict(slot)
        hs = KV.prefix_block_hashes(ids, self.block)
        if not hs:
            return
        self._slot_ids[slot] = tuple(ids)
        self._slot_hashes[slot] = tuple(hs)
        for h in hs:
            self._by_hash.setdefault(h, set()).add(slot)

    def evict(self, slot: int) -> None:
        hs = self._slot_hashes.pop(slot, None)
        self._slot_ids.pop(slot, None)
        if not hs:
            return
        for h in hs:
            slots = self._by_hash.get(h)
            if slots is not None:
                slots.discard(slot)
                if not slots:
                    del self._by_hash[h]

    def match(self, ids) -> "tuple[Optional[int], int]":
        """(slot, n_tokens) of the longest cached block-aligned PROPER
        prefix of ``ids`` — capped at ``len(ids) - 1`` so at least one
        tail token always prefills (the first sampled token comes from
        the last prompt token's prefill logits)."""
        hs = KV.prefix_block_hashes(ids, self.block)
        usable = min(len(hs), (len(ids) - 1) // self.block)
        for b in range(usable, 0, -1):
            slots = self._by_hash.get(hs[b - 1])
            if not slots:
                continue
            n = b * self.block
            want = tuple(ids[:n])
            for slot in sorted(slots):
                if self._slot_ids.get(slot, ())[:n] == want:
                    return slot, n
        return None, 0


# Jitted cross-slot prefix-copy steps, one per static gather width (the
# same power-of-two bucket ladder as chunk widths). Module-level so pool
# replicas and fleet reruns share compiles; _track_retraces folds their
# signature counts into stats["jit_retraces"]. Pool replicas pump on
# ThreadPoolExecutor workers, so the dict is mutated concurrently with
# another replica's _track_retraces iteration — all access goes through
# _COPY_LOCK (declared below, enforced by reprolint's thread-ownership
# rule).
_COPY_JITS: Dict[int, object] = {}
_COPY_LOCK = threading.Lock()
_MODULE_OWNERSHIP = {"_COPY_JITS": "shared-lock:_COPY_LOCK"}


def _jit_copy(width: int):
    with _COPY_LOCK:
        fn = _COPY_JITS.get(width)
        if fn is None:
            def copy_fn(cache, src_idx, dst_idx, length):
                # every pool array: K and V, or the latent c_kv and k_pe
                return {n: KV.copy_prefix(x, src_idx, dst_idx, length, width)
                        for n, x in cache.items()}
            fn = jax.jit(copy_fn, donate_argnums=(0,))
            _COPY_JITS[width] = fn
        return fn


@functools.lru_cache(maxsize=64)
def _jit_steps(cfg: ModelConfig, max_len: int, use_pallas: bool = False):
    """Fused decode+sample and chunk-prefill+sample steps, jitted once per
    (config, max_len, attention backend) and shared by every engine
    instance — compile cache survives engine churn (fleet drivers build
    engine pairs per run). ``use_pallas`` is part of the cache key because
    the kernel dispatch is read at trace time: without it, toggling
    ``pallas_enabled`` after a reference-path compile would silently keep
    serving XLA programs."""

    def decode_fn(params, tokens, pos, cache, key, temps, live):
        # park inactive/prefilling slots at max_len-1: their garbage write
        # lands on a line no live request ever attends (requests finish at
        # pos >= max_len-1 before reading it)
        pos_eff = jnp.where(live > 0, pos, max_len - 1)
        logits, cache, load = M.serve_decode_counted(params, cfg, tokens,
                                                     pos_eff, cache, live)
        key, sub = jax.random.split(key)
        nxt = _device_sample(logits[:, 0], sub, temps)
        if load is None:
            return nxt, pos + live, cache, key
        # expert stacks: the step's expert load rides out with the ids
        return nxt, pos + live, cache, key, load

    def prefill_fn(params, tokens, slot_idx, pos0, take, pos, cache, key,
                   temps, kv_width):
        logits, cache = M.serve_prefill_chunk(params, cfg, tokens, cache,
                                              slot_idx, pos0, take,
                                              kv_width=kv_width)
        key, sub = jax.random.split(key)
        first = _device_sample(logits[:, 0], sub, temps)
        pos = pos.at[slot_idx].set(pos0 + take)
        return first, pos, cache, key

    # donate pos + cache: XLA aliases the buffers, so the per-step KV
    # update is in place instead of a full-pool copy; kv_width is static
    # (a power-of-two bucket) so attention shapes stay bounded
    return (jax.jit(decode_fn, donate_argnums=(2, 3)),
            jax.jit(prefill_fn, donate_argnums=(5, 6),
                    static_argnums=(9,)))


class ServingEngine:
    """Slot-based continuous batching engine for one model."""

    # Concurrency contract, enforced statically by reprolint's
    # thread-ownership rule (tools/reprolint/README.md): when this
    # engine is an EnginePool replica, step()/pump() run on a
    # ThreadPoolExecutor worker, so everything the step path touches is
    # replica-private — owned by that worker while a pool pump is in
    # flight, and never reachable through another object reference from
    # code running concurrently with workers.
    _THREAD_OWNERSHIP = {
        "cache": "replica-private",
        "pos": "replica-private",
        "_pos_np": "replica-private",
        "key": "replica-private",
        "active": "replica-private",
        "queue": "replica-private",
        "_prefilling": "replica-private",
        "_pending_copy": "replica-private",
        "_pinned": "replica-private",
        "_prefix": "replica-private",
        "_slot_used": "replica-private",
        "stats": "replica-private",
    }
    # worker-thread entry points; reprolint closes the set over self.x()
    # calls, so every helper the step path reaches is checked too
    _WORKER_METHODS = ("step", "pump")

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 512, dtype=jnp.float32, seed: int = 0,
                 prefill_chunk: Optional[int] = None,
                 batched_prefill: bool = True,
                 prefix_reuse: bool = True,
                 prefix_block: int = KV.PREFIX_BLOCK):
        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.dtype = dtype
        self.seed = seed
        # raw ctor args so EnginePool can clone replicas (shared params,
        # independent KV slot pools); batched_prefill below is ANDed with
        # the family gate, so keep the caller's value here
        self._ctor_kw = dict(batch_slots=batch_slots, max_len=max_len,
                             dtype=dtype, prefill_chunk=prefill_chunk,
                             batched_prefill=batched_prefill,
                             prefix_reuse=prefix_reuse,
                             prefix_block=prefix_block)
        self.key = jax.random.PRNGKey(seed)
        self.cache = M.init_cache(cfg, batch_slots, max_len, dtype=dtype)
        # device-resident next positions (int32), parked at max_len-1 for
        # slots with no live request; host mirror for cheap finish checks
        self.pos = jnp.full((batch_slots,), max_len - 1, jnp.int32)
        self._pos_np = np.full(batch_slots, max_len - 1, np.int32)
        self.prefill_chunk = (None if prefill_chunk is None
                              else max(1, min(prefill_chunk, max_len)))
        self.batched_prefill = batched_prefill and M.chunked_prefill_ok(cfg)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.queue: List[Request] = []
        self._rid = 0
        self._slot_used = [False] * batch_slots
        self._prefilling: Dict[int, _PrefillJob] = {}
        # cross-request KV prefix reuse: only the batched-prefill fast path
        # can seed a slot (the legacy path rebuilds a batch-1 cache from
        # scratch). The slot pool itself is always absolute-positioned in
        # the serving regime (pos < max_len, so even windowed configs
        # write line pos % M == pos), and window masking reads the same
        # lines either way — content-identical caches keep bit-identity.
        self.prefix_block = max(1, prefix_block)
        self.prefix_reuse = bool(prefix_reuse and self.batched_prefill)
        self._prefix = _PrefixIndex(self.prefix_block)
        self._pending_copy: List[tuple] = []   # (dst_slot, src_slot, n)
        self._pinned: set = set()              # copy sources awaiting launch
        self.stats = {"tokens_out": 0, "prefill_tokens": 0, "steps": 0,
                      "slot_reuses": 0, "peak_active": 0, "requests": 0,
                      "prefill_calls": 0, "prefill_batch_max": 0,
                      "prefill_backend": None, "jit_retraces": 0,
                      "prefix_hits": 0, "prefill_tokens_saved": 0,
                      "prefix_copies": 0,
                      # rows and positions the step programs compute,
                      # padding included: beside tokens_out and
                      # prefill_tokens they give the padded share
                      "decode_rows": 0, "prefill_positions": 0}
        if cfg.family == "moe" and M.chunked_prefill_ok(cfg):
            # decode's expert load, summed over steps and layers: the
            # (token, expert) pairs each held expert computed, and the
            # held experts that computed any
            self.stats.update({f"expert_picks_{i}": 0
                               for i in range(cfg.experts_held)},
                              experts_hit=0)

    def _steps(self):
        """Resolve the jitted step pair against the CURRENT kernel-dispatch
        state (lru-cached, so this is a dict hit per tick)."""
        from repro.kernels import dispatch as kd
        return _jit_steps(self.cfg, self.max_len, kd.use_pallas())

    def _track_retraces(self) -> None:
        """Record how many signatures the shared jitted step pair has
        compiled. Every distinct (g, width, kv_width) prefill shape and
        every decode shape is one XLA program; all three come off static
        bucket ladders (g <= slots; width/kv_width powers of two capped
        at max_len), so this must stay bounded for ANY fleet mix — the
        regression test pins the bound. The count is the lru-shared
        truth for this (cfg, max_len, backend) key, so engine churn
        (pool replicas, fleet reruns) must not grow it either."""
        decode_step, prefill_step = self._steps()
        self.stats["jit_retraces"] = (decode_step._cache_size()
                                      + prefill_step._cache_size())
        # prefix-seed copy compiles tracked separately: _COPY_JITS is
        # shared process-wide across (cfg, max_len) shapes, so folding it
        # into jit_retraces would couple one engine's bound to every
        # other engine's compile history. Its ladder is (g, width) —
        # bounded exactly like prefill — and the no-new-compiles-on-rerun
        # contract is pinned by the retrace regression test. Snapshot the
        # shared dict under its lock: another pool replica's worker may
        # be inserting a new width mid-iteration.
        with _COPY_LOCK:
            fns = list(_COPY_JITS.values())
        self.stats["prefix_seed_compiles"] = sum(
            fn._cache_size() for fn in fns)

    def clone(self, *, seed: Optional[int] = None) -> "ServingEngine":
        """A fresh engine over the SAME config and params (no re-init)
        with its own KV slot pool — the EnginePool replica constructor."""
        return ServingEngine(self.cfg, self.params,
                             seed=self.seed if seed is None else seed,
                             **self._ctor_kw)

    # ---- public API ---------------------------------------------------
    def submit(self, prompt: "str | List[int]", *, max_new_tokens: int = 32,
               temperature: float = 0.0,
               prefix_hint: Optional[List[int]] = None) -> Request:
        # prefix_hint is a pool/scheduler affinity signal (see
        # EnginePool.submit); a single engine matches against the actual
        # prompt at admit time, so the hint is accepted and ignored here.
        del prefix_hint
        if max_new_tokens >= self.max_len - 1:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} leaves no room for the "
                f"prompt in a max_len={self.max_len} cache (need "
                f"max_new_tokens <= max_len - 2)")
        ids = tok.encode(prompt) if isinstance(prompt, str) else list(prompt)
        ids = [min(i, self.cfg.vocab_size - 1) for i in ids]
        req = Request(self._rid, ids, max_new_tokens, temperature)
        req._engine = self            # ownership marker for run_until
        self._rid += 1
        self.queue.append(req)
        return req

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(a is not None for a in self.active)

    @property
    def capacity(self) -> int:
        """KV slots this engine can decode concurrently (pool symmetry)."""
        return self.slots

    @property
    def load(self) -> int:
        """Requests holding or waiting on a slot (active + queued)."""
        return self.n_active + len(self.queue)

    # reprolint: hot
    def pump(self) -> bool:
        """Advance one step if there is work. Returns progress (the same
        surface ``EnginePool.pump`` exposes for a whole replica set)."""
        if self.has_work:
            self.step()
            return True
        return False

    def saturated(self) -> bool:
        """EngineLike surface: every KV slot is leased (a pool is
        saturated only when every replica is)."""
        return self.load >= self.slots

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.has_work:
                break
            done.extend(self.step())
        return done

    def run_until(self, req: Request, max_steps: int = 10_000) -> Request:
        """Step the engine until ``req`` finishes (continuous batching:
        co-resident requests from other queries advance on the same decode
        steps — the fleet runtime's slot-sharing entry point)."""
        if getattr(req, "_engine", None) is not self:
            raise ValueError(
                f"request {req.rid} was never submitted to this engine "
                f"(submit() returns the Request object to wait on)")
        for _ in range(max_steps):
            if req.done:
                return req
            if not self.has_work:
                raise RuntimeError(
                    f"engine drained with request {req.rid} unfinished "
                    f"(engine bug: an owned request left the queue)")
            self.step()
        if req.done:
            return req
        raise RuntimeError(f"request {req.rid} did not finish "
                           f"within {max_steps} engine steps")

    @property
    def n_active(self) -> int:
        return sum(a is not None for a in self.active)

    def prefix_match_len(self, ids) -> int:
        """Longest block-aligned cached prefix (in tokens) this engine
        could seed for ``ids`` right now — the pool's affinity signal.
        Read-only: no pin, no eviction, no stats."""
        if not self.prefix_reuse or not ids:
            return 0
        _, n = self._prefix.match(list(ids))
        return n

    def cancel(self, req: Request) -> bool:
        """Withdraw a request: drop it from the admission queue, or free
        its KV slot (and any in-progress prefill) if already resident —
        the slot returns to the pool on the next admit. Decoded tokens
        stay on the request; the caller decides whether to discard them.
        Safe to call on an already-finished or foreign request (no-op,
        returns False)."""
        for j, q in enumerate(self.queue):
            if q is req:
                self.queue.pop(j)
                req._engine = None
                return True
        for slot, r in enumerate(self.active):
            if r is req:
                self.active[slot] = None
                self._prefilling.pop(slot, None)
                # drop any not-yet-launched prefix seed targeting this slot
                # and recompute pins (a source stays pinned only while some
                # other borrower still needs it)
                if self._pending_copy:
                    self._pending_copy = [c for c in self._pending_copy
                                          if c[0] != slot]
                    self._pinned = {src for _, src, _ in self._pending_copy}
                req._engine = None
                return True
        return False

    # ---- engine internals ----------------------------------------------
    def _admit(self) -> None:
        with span("engine.admit"):
            for slot in range(self.slots):
                if (self.active[slot] is None and self.queue
                        and slot not in self._pinned):
                    req = self.queue.pop(0)
                    self.active[slot] = req
                    # slot lease accounting: KV lines are a fixed pool; a
                    # reused slot means the cache allocation was recycled
                    # rather than grown (the bounded-pool invariant)
                    if self._slot_used[slot]:
                        self.stats["slot_reuses"] += 1
                    self._slot_used[slot] = True
                    self.stats["requests"] += 1
                    ids = req.prompt_ids[
                        -(self.max_len - req.max_new_tokens - 1):]
                    if self.batched_prefill:
                        job = _PrefillJob(ids)
                        if self.prefix_reuse:
                            # match BEFORE evicting this slot's own entry: if
                            # the best source is the slot we just leased, its
                            # prefix lines are already in place (in-place
                            # reuse, no copy); otherwise pin the source so no
                            # later lease overwrites it before the batched
                            # seed copy launches.
                            src, n = self._prefix.match(ids)
                            if n > 0:
                                job.off = n
                                self.stats["prefix_hits"] += 1
                                self.stats["prefill_tokens_saved"] += n
                                if src != slot:
                                    self._pending_copy.append((slot, src, n))
                                    self._pinned.add(src)
                            self._prefix.evict(slot)
                        self._prefilling[slot] = job
                    else:
                        self._prefill_slot_legacy(slot, req, ids)
            self.stats["peak_active"] = max(self.stats["peak_active"],
                                            self.n_active)

    def _bucket(self, n: int) -> int:
        """Pad chunk width to a power-of-two bucket (bounded jit compiles)."""
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_len)

    # reprolint: hot
    def _prefill_launch(self) -> Optional[_PrefillPass]:
        """Launch one chunk for every prefilling slot — a single padded
        ``serve_prefill_chunk`` call for the whole group. Host bookkeeping
        is deferred to ``_prefill_commit`` so a pool can overlap another
        replica's launch with this one's device compute."""
        with span("engine.prefill_launch"):
            if self._pending_copy:
                # seed newly leased slots from their matched sources in ONE
                # batched copy, issued BEFORE this step's prefill writes: the
                # copy reads the pre-step cache value (XLA data ordering), so
                # even a source re-leased in the same admit pass is read
                # intact. Pins release here — after this the borrowers own
                # their lines and sources are free to be overwritten.
                dst = np.asarray([c[0] for c in self._pending_copy], np.int32)
                src = np.asarray([c[1] for c in self._pending_copy], np.int32)
                ln = np.asarray([c[2] for c in self._pending_copy], np.int32)
                width = self._bucket(int(ln.max()))
                self.cache = _jit_copy(width)(
                    self.cache, jnp.asarray(src), jnp.asarray(dst),
                    jnp.asarray(ln))  # donate+rebind: reprolint-clean idiom
                self.stats["prefix_copies"] += len(self._pending_copy)
                self._pending_copy.clear()
                self._pinned.clear()
                self._track_retraces()
            if not self._prefilling:
                return None
            jobs = sorted(self._prefilling.items())
            chunk = self.prefill_chunk or self.max_len
            take = [min(j.remaining, chunk) for _, j in jobs]
            width = self._bucket(max(take))
            g = len(jobs)
            tokens = np.zeros((g, width), np.int32)
            pos0 = np.zeros(g, np.int32)
            slot_idx = np.zeros(g, np.int32)
            temps = np.zeros(g, np.float32)
            for i, (slot, j) in enumerate(jobs):
                tokens[i, :take[i]] = j.ids[j.off:j.off + take[i]]
                pos0[i] = j.off
                slot_idx[i] = slot
                temps[i] = self.active[slot].temperature
            # kv_width is pinned to the SAME power-of-two bucket ladder as
            # the chunk width (static jit arg): a mixed-length fleet can only
            # ever produce O(log(max_len)) distinct kv_width values, so the
            # (g, width, kv_width) retrace space stays bounded no matter how
            # prompt lengths vary step to step (stats["jit_retraces"]).
            kv_width = self._bucket(int(max(pos0[i] + take[i]
                                            for i in range(g))))
            from repro.kernels import dispatch as kd
            self.stats["prefill_backend"] = ("pallas" if kd.use_pallas()
                                             else "xla")
            _, prefill_step = self._steps()
            first, self.pos, self.cache, self.key = prefill_step(
                self.params, jnp.asarray(tokens), jnp.asarray(slot_idx),
                jnp.asarray(pos0),
                # host->device upload of a Python list, not a device sync
                # reprolint: disable=host-sync-in-hot-path -- take is a host list; np.asarray builds the upload buffer
                jnp.asarray(np.asarray(take, np.int32)),
                self.pos, self.cache, self.key, jnp.asarray(temps), kv_width)
            self.stats["prefill_calls"] += 1
            self.stats["prefill_positions"] += g * width
            self.stats["prefill_batch_max"] = max(
                self.stats["prefill_batch_max"], g)
            self._track_retraces()
            return _PrefillPass(jobs, take, first)

    # reprolint: hot
    def _prefill_commit(self, p: _PrefillPass) -> None:
        """Sync the launched prefill chunk and advance the per-slot jobs
        (first sampled token, slot positions, finished-job retirement)."""
        with span("engine.sync"):
            # reprolint: disable=host-sync-in-hot-path -- the ONE host transfer per prefill pass (sampled first tokens)
            first_np = np.asarray(p.first)
        for i, (slot, j) in enumerate(p.jobs):
            j.off += p.take[i]
            self.stats["prefill_tokens"] += p.take[i]
            if j.remaining == 0:
                self.active[slot].output_ids.append(int(first_np[i]))
                self._pos_np[slot] = len(j.ids)
                if self.prefix_reuse:
                    # the slot's prompt lines are now fully written (and
                    # stable: decode only appends past them) — publish
                    # them for later leases to borrow
                    self._prefix.register(slot, j.ids)
                del self._prefilling[slot]

    def _prefill_slot_legacy(self, slot: int, req: Request,
                             ids: List[int]) -> None:
        """Single-request batch-1 prefill + slot copy — the reference path
        for families without chunked-slot prefill support."""
        batch = {"tokens": jnp.asarray([ids], jnp.int32)}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (1, self.cfg.n_image_patches, self.cfg.d_model), self.dtype)
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.encoder_seq, self.cfg.d_model), self.dtype)
        cache1 = M.init_cache(self.cfg, 1, self.max_len, dtype=self.dtype)
        logits, cache1 = M.serve_prefill(self.params, self.cfg, batch, cache1)
        # copy slot lines: every cache leaf has batch at axis -? => leaves
        # follow [L, B, ...] or [B, ...]; match by dim size
        def write(dst, src):
            if dst.ndim >= 2 and dst.shape[1] == self.slots and src.shape[1] == 1:
                return dst.at[:, slot].set(src[:, 0])
            if dst.shape[0] == self.slots and src.shape[0] == 1:
                return dst.at[slot].set(src[0])
            # nested stacks ([G, m, B, ...]): search batch axis
            for ax in range(dst.ndim):
                if dst.shape[ax] == self.slots and src.shape[ax] == 1:
                    idx = tuple([slice(None)] * ax + [slot])
                    sidx = tuple([slice(None)] * ax + [0])
                    return dst.at[idx].set(src[sidx])
            raise ValueError(f"no batch axis: {dst.shape} <- {src.shape}")

        self.cache = jax.tree.map(write, self.cache, cache1)
        self.stats["prefill_backend"] = "legacy-batch1"
        n_img = self.cfg.n_image_patches if self.cfg.family == "vlm" else 0
        n = len(ids) + n_img
        self.pos = self.pos.at[slot].set(n)
        self._pos_np[slot] = n
        self.stats["prefill_tokens"] += len(ids)
        req.output_ids.append(self._sample_host(logits[0, -1], req))

    # reprolint: hot
    def _sample_host(self, logits, req: Request) -> int:
        """Host-side sampling (legacy prefill path only)."""
        # reprolint: disable=host-sync-in-hot-path -- legacy batch-1 path samples on host by design (reference behavior)
        logits = np.asarray(logits, np.float32)
        if req.temperature <= 0:
            return int(np.argmax(logits))
        self.key, k = jax.random.split(self.key)
        # reprolint: disable=host-sync-in-hot-path -- legacy path: one sampled id comes back to host here
        return int(jax.random.categorical(
            k, jnp.asarray(logits) / req.temperature))

    # reprolint: hot
    def _decode_launch(self) -> Optional[_DecodePass]:
        """Launch one decode token for every live (fully prefilled) slot;
        host bookkeeping is deferred to ``_decode_commit``."""
        with span("engine.decode_launch"):
            live_slots = [i for i, r in enumerate(self.active)
                          if r is not None and i not in self._prefilling]
            if not live_slots:
                return None
            tokens = np.zeros((self.slots, 1), np.int32)
            temps = np.zeros(self.slots, np.float32)
            live = np.zeros(self.slots, np.int32)
            for i in live_slots:
                tokens[i, 0] = self.active[i].output_ids[-1]
                temps[i] = self.active[i].temperature
                live[i] = 1
            decode_step, _ = self._steps()
            nxt, self.pos, self.cache, self.key, *load = decode_step(
                self.params, jnp.asarray(tokens), self.pos, self.cache,
                self.key, jnp.asarray(temps), jnp.asarray(live))
            self.stats["decode_rows"] += self.slots
            self._track_retraces()
            return _DecodePass(live_slots, nxt, load[0] if load else None)

    # reprolint: hot
    def _decode_commit(self, d: _DecodePass) -> List[Request]:
        """Sync the launched decode step and retire finished requests."""
        with span("engine.sync"):
            # reprolint: disable=host-sync-in-hot-path -- the ONE host transfer per decode step (sampled ids, and the expert load where there is one)
            nxt_np, load_np = jax.device_get((d.nxt, d.load))
        if load_np is not None:
            for i in range(len(load_np) - 1):
                self.stats[f"expert_picks_{i}"] += int(load_np[i])
            self.stats["experts_hit"] += int(load_np[-1])
        finished: List[Request] = []
        for i in d.live_slots:
            req = self.active[i]
            req.output_ids.append(int(nxt_np[i]))
            self._pos_np[i] += 1
            self.stats["tokens_out"] += 1
            if (len(req.output_ids) >= req.max_new_tokens
                    or nxt_np[i] == tok.EOS_ID
                    or self._pos_np[i] >= self.max_len - 1):
                req.done = True
                finished.append(req)
                self.active[i] = None
        self.stats["steps"] += 1
        return finished

    # reprolint: hot
    def step(self) -> List[Request]:
        """One engine iteration: admit waiting requests, advance every
        prefilling slot by one chunk, then decode one token for all live
        slots (prefill and decode of co-resident requests interleave, so
        a long prompt never stalls running generations)."""
        with span("engine.step"):
            self._admit()
            p = self._prefill_launch()
            if p is not None:
                self._prefill_commit(p)
            d = self._decode_launch()
            return self._decode_commit(d) if d is not None else []


@dataclass
class _Inflight:
    """Future for one subtask submitted to a JAXExecutor engine."""

    req: Request
    sid: int
    cloud: bool
    difficulty: float
    n_bad_parents: int
    query: object
    t0: float


class JAXExecutor:
    """HybridFlow Executor backed by a real ServingEngine or EnginePool.

    Correctness still comes from the world model (we cannot grade free-form
    text without a verifier), but latency is *measured* wall-clock of real
    model execution, and cost is token-metered from real token counts —
    the integration point the paper's 'system shifts' calibration needs.

    One executor (and its engine/pool) is shared by *all* queries in a
    fleet: each subtask leases a KV slot from a fixed slot pool. Two ways
    to drive it:

    * ``run`` — synchronous: submits and steps the engine until the
      subtask's own request finishes (``run_until``); co-residency then
      only arises from engine-level callers.
    * ``submit``/``poll``/``pump`` — the async surface the fleet
      scheduler's pump loop uses: ``submit`` enqueues and returns a
      future, ``pump`` advances the engine (every pool replica with
      pending work) one step, ``poll`` collects a finished future.
      Subtasks from different queries submitted before the next pump
      decode in the SAME micro-batches, so wall-clock tracks the
      simulated makespan instead of serializing.

    ``concurrency=None`` derives the dispatch width from the backing
    capacity — ``slots`` for a single engine, ``replicas × slots`` for an
    ``EnginePool`` — so the fleet scheduler admits exactly as many
    subtasks as there are KV slots. ``saturated()`` reports live slot
    occupancy: the scheduler's cloud→edge spill consults it so spill only
    fires when *every* replica is really full, not merely when the
    scheduler's own busy count hit an explicit (possibly narrower)
    ``concurrency`` cap.
    """

    def __init__(self, engine: "EngineLike", wm, cloud: bool,
                 concurrency: Optional[int] = None, price_out: float = 0.0):
        self.engine: "EngineLike" = engine
        self.wm = wm
        self.cloud = cloud
        # derived caps track capacity if the engine is later pooled; an
        # explicit cap is a caller admission policy and must survive it
        self.derived_concurrency = concurrency is None
        self.concurrency = engine.capacity if concurrency is None \
            else concurrency
        self.price_out = price_out

    def saturated(self) -> bool:
        """True when no replica has a free KV slot (spill eligibility).
        Uniform across backings: ``EngineLike.saturated()`` is the
        protocol method both ``ServingEngine`` and ``EnginePool``
        implement, so there is no engine-vs-pool branching here."""
        return bool(self.engine.saturated())

    # sibling subtasks of one query share this many leading characters of
    # query context verbatim, so their prompts hash to the same KV prefix
    # blocks (kept short: engine prompts are tail-truncated to the KV
    # budget, and a truncated-away context can never be shared)
    QUERY_CTX_CHARS = 40

    # advertises the ``prefix_hint=`` submit kwarg to the fleet scheduler
    # (analytic executors don't take it; the scheduler feature-detects)
    accepts_prefix_hint = True

    @classmethod
    def query_context(cls, query) -> str:
        """The verbatim prompt prefix every sibling subtask of ``query``
        starts with — the DAG-level shared context."""
        txt = getattr(query, "text", "") or ""
        return (txt[:cls.QUERY_CTX_CHARS] + " >> ") if txt else ""

    def shared_context(self, query) -> List[int]:
        """Token ids of :meth:`query_context` — the prefix hint the fleet
        scheduler pins on a dispatch and carries across retry, cloud→edge
        spill, and degradation re-dispatch."""
        return tok.encode(self.query_context(query))

    # ---- async surface (fleet pump loop) -------------------------------
    def submit(self, query, node, dep_results, *,
               prefix_hint: Optional[List[int]] = None) -> _Inflight:
        from repro.core.scheduler import _subtask_of
        with span("exec.submit", qid=query.qid, sid=node.sid):
            st = _subtask_of(query, node)
            prompt = (self.query_context(query) + node.desc + " || "
                      + " ; ".join(dep_results[d].answer for d in node.deps
                                   if d in dep_results))
            n_bad = sum(1 for d in node.deps
                        if d in dep_results and not dep_results[d].correct)
            req = self.engine.submit(prompt,
                                     max_new_tokens=min(st.tok_out, 48),
                                     prefix_hint=prefix_hint)
        return _Inflight(req, st.sid, self.cloud, st.difficulty, n_bad,
                         query, time.perf_counter())

    # reprolint: hot
    def pump(self) -> bool:
        """Advance the engine (or every loaded pool replica) one step if
        it has work. Returns progress."""
        return bool(self.engine.pump())

    def cancel(self, h: _Inflight) -> bool:
        """Withdraw a (timed-out) attempt so its KV slot frees now — the
        fleet scheduler's deadline path calls this before re-dispatch."""
        return bool(self.engine.cancel(h.req))

    def attempt_cost(self, h: _Inflight) -> float:
        """$ already sunk into an attempt: tokens decoded so far. The
        scheduler charges this for abandoned (timed-out) attempts so the
        budget model stays honest under faults."""
        return len(h.req.output_ids) * self.price_out if self.cloud else 0.0

    # reprolint: hot
    def poll(self, h: _Inflight):
        """Collect a finished future; None while still decoding."""
        if not h.req.done:
            return None
        from repro.core.scheduler import SubtaskResult
        latency = time.perf_counter() - h.t0
        prof = self.wm.profile(int(self.cloud))
        p = prof.p_correct(h.difficulty)
        p *= self.wm.parent_penalty ** h.n_bad_parents
        u = self.wm._u(h.query, h.sid)
        n_out = len(h.req.output_ids)
        cost = n_out * self.price_out if self.cloud else 0.0
        return SubtaskResult(h.sid, int(self.cloud), bool(u < p), latency,
                             cost, len(h.req.prompt_ids), n_out,
                             answer=h.req.text[:120])

    # ---- synchronous surface (Executor protocol) -----------------------
    def run(self, query, node, dep_results):
        h = self.submit(query, node, dep_results)
        self.engine.run_until(h.req)
        return self.poll(h)
