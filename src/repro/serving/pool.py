"""Replicated serving-engine pool: sharded cloud capacity with
replica-aware dispatch.

HybridFlow's cloud side was one ``ServingEngine`` with N slots, so
"cloud concurrency" was really a single replica's batch width. An
``EnginePool`` owns R engine replicas — **shared params** (one pytree,
no re-init) but **independent KV slot pools** — and exposes the same
``submit`` / ``has_work`` / ``step`` surface as a single engine, so
``JAXExecutor`` and the fleet scheduler drive either interchangeably.

Dispatch contract
-----------------
* **Least-loaded replica selection** — ``submit`` routes each request to
  the replica with the smallest *load* (active + queued requests); ties
  break to the lowest replica index. Selection is a pure function of the
  pool's current occupancy, so a given submit/step sequence is
  deterministic.
* **R = 1 identity** — a one-replica pool performs exactly the single
  engine's admit → prefill → decode sequence per ``step``; greedy tokens
  are bit-identical to driving the lone ``ServingEngine`` directly
  (tested through the live ``FleetScheduler``).
* **Pump pass** — ``pump()``/``step()`` advance *every* replica with
  pending work in one pass. With ``threads=True`` (default) each loaded
  replica's step runs on its own worker thread: jitted execution
  releases the GIL, so replica computes overlap on multi-core hosts the
  way they would on per-replica accelerators — two half-full replicas
  cost one step's wall-clock, not two. Replica state is strictly
  thread-private (each worker touches only its own engine) and finished
  requests are collected in replica-index order, so token streams and
  completion order stay deterministic. ``threads=False`` falls back to
  a sequential launch-all/commit-all pass: all replicas' prefill chunks
  are dispatched before any is synced, then all decode steps likewise,
  letting JAX's async dispatch overlap one replica's host-side commit
  with the next replica's device compute.
* **Saturation** — ``all_saturated`` is True only when every replica's
  load has reached its slot count. ``JAXExecutor.saturated()`` forwards
  it to the fleet scheduler, whose cloud→edge spill fires only then:
  a pool with any free replica slot keeps cloud-routed work on the
  cloud.
* **Occupancy stats** — ``occupancy()`` reports per-replica slot-lease
  state (active / queued / free / requests / slot_reuses / peak_active /
  health); ``stats`` aggregates the replicas' counters into one
  engine-shaped dict (plus ``replicas`` and ``pump_passes``) for drop-in
  reporting.

Failure semantics
-----------------
Every replica carries a health state: **healthy → suspect → dead**. A
replica whose step *raises* (in the thread pump, the single-loaded fast
path, or any phase of the sequential pass) is marked **dead**: the
exception is captured — never lost in a worker thread, never allowed to
strand sibling replicas' finished requests — and with ``failover=True``
(default) the dead replica's in-flight work (active slots in slot order,
then queue FIFO) is re-submitted to the least-loaded survivor, restarted
from the prompt (decoded tokens are discarded; generation state lives in
the replica's KV slots, which died with it). With ``failover=False`` the
captured exception re-raises from ``step`` instead. When every replica
is dead, ``step``/``submit`` raise. ``suspect_after=N`` arms straggler
detection: a replica that holds work but makes no progress for N
consecutive pool passes turns **suspect** — its work is hedged onto
strictly-healthy replicas and dispatch deprioritizes it until it makes
progress again (suspect is reversible; dead is not). All transitions
land in ``pool_stats`` (deaths / failovers / suspects / hedges /
replica_errors).

``EnginePool.replicate`` builds R fresh replicas from a config + params;
``EnginePool.like`` scales out an existing engine, keeping it as replica
0 (external handles to it stay live) and cloning R-1 siblings with
distinct sampling seeds.

Elasticity
----------
``arm_autoscale(AutoscalePolicy(...))`` makes the pool *elastic*: each
replica carries a lifecycle state — **warm** (serving), **warming**
(paying the modeled cold start), **cold** (scaled down) — orthogonal to
its health state. An :class:`Autoscaler` ticks on the pool's wall clock
(every ``pump``/``step``/``submit``) and:

* **grows** — starts warming a cold replica when live load presses on
  the warm+warming capacity (``load > capacity * scale_up_at``); the
  replica serves only after the modeled :class:`ColdStartModel` phases
  (boot + model load + first inference) have elapsed;
* **shrinks** — cools an idle warm replica when occupancy drops under
  ``scale_down_at`` (never below ``max(min_replicas, 1)`` this way);
* **scales to zero** — with ``min_replicas=0``, a traffic gap longer
  than ``idle_to_zero_s`` cools every warm replica;
* **pokes** — the first ``submit`` after a gap finds no warm/warming
  replica and starts one warming ("poke-to-warm"); the request queues
  on it and waits out the cold start.

Only *warm* replicas step; warming/cold replicas hold queued work
without progress (straggler detection skips them). Scale-down is a
*model*: replicas share one host here, so cooling stops a replica's
passes and charges the re-warm cost without actually releasing its KV
memory — the cost accounting, not the allocator, is the contract.
Every decision lands in ``Autoscaler.events`` / ``pool_stats`` and is
surfaced through the runtime report.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.serving.engine import Request, ServingEngine
from repro.tracing import span


@dataclass(frozen=True)
class ColdStartModel:
    """Modeled cost of bringing a cold replica up, split into the three
    phases worth modeling separately (boot, weight load, first-inference
    warm-up/compile); a warming replica serves only after all three."""

    boot_s: float = 0.4
    model_load_s: float = 0.8
    first_infer_s: float = 0.3

    @property
    def total_s(self) -> float:
        return self.boot_s + self.model_load_s + self.first_infer_s


@dataclass(frozen=True)
class AutoscalePolicy:
    """Occupancy-driven elasticity policy for an :class:`EnginePool`.

    ``scale_up_at`` / ``scale_down_at`` are load fractions of the
    current warm+warming slot capacity; ``max_replicas=None`` means the
    pool's full replica count. ``min_replicas=0`` enables scale-to-zero
    after ``idle_to_zero_s`` of an empty pool.
    """

    min_replicas: int = 0
    max_replicas: Optional[int] = None
    scale_up_at: float = 0.8
    scale_down_at: float = 0.25
    idle_to_zero_s: float = 1.0
    decision_interval_s: float = 0.05
    cold_start: ColdStartModel = field(default_factory=ColdStartModel)

    def __post_init__(self):
        if self.min_replicas < 0:
            raise ValueError("min_replicas must be >= 0")
        if not (0.0 < self.scale_up_at <= 1.0):
            raise ValueError("scale_up_at must be in (0, 1]")
        if not (0.0 <= self.scale_down_at < self.scale_up_at):
            raise ValueError("scale_down_at must be in [0, scale_up_at)")


class Autoscaler:
    """Grows/shrinks an :class:`EnginePool`'s warm replica set from live
    occupancy. Pure bookkeeping over the pool's lifecycle list — ticked
    from ``pump``/``step``/``submit``, no thread of its own. ``clock``
    is injectable so tests can drive transitions deterministically."""

    def __init__(self, pool: "EnginePool", policy: AutoscalePolicy,
                 clock: Optional[Callable[[], float]] = None):
        self.pool = pool
        self.policy = policy
        self.clock = clock if clock is not None else time.perf_counter
        self._t0 = self.clock()
        self.events: List[Tuple[float, str, int]] = []  # (t, action, replica)
        self.counters: Dict[str, int] = {
            "scale_ups": 0, "scale_downs": 0, "scale_to_zero": 0,
            "pokes": 0, "promotions": 0}
        self._ready_at: Dict[int, float] = {}
        self._idle_since: Optional[float] = None
        self._last_decision = float("-inf")
        n_warm = min(max(policy.min_replicas, 0), pool.n_replicas)
        for i in range(pool.n_replicas):
            pool.lifecycle[i] = "warm" if i < n_warm else "cold"

    def _now(self) -> float:
        return self.clock() - self._t0

    def _log(self, now: float, action: str, i: int) -> None:
        self.events.append((round(now, 4), action, i))

    def _start_warming(self, i: int, now: float, action: str) -> None:
        self.pool.lifecycle[i] = "warming"
        self._ready_at[i] = now + self.policy.cold_start.total_s
        self.counters["scale_ups"] += 1
        self._log(now, action, i)

    def poke(self) -> Optional[int]:
        """First arrival after a gap: start warming one cold replica so
        the queued request has somewhere to land. Returns its index."""
        pool = self.pool
        cold = [i for i in pool._alive() if pool.lifecycle[i] == "cold"]
        if not cold:
            return None
        self.counters["pokes"] += 1
        self._start_warming(cold[0], self._now(), "poke")
        return cold[0]

    def tick(self) -> None:
        now = self._now()
        pool, p = self.pool, self.policy
        # promotions first: a warming replica whose cold start has
        # elapsed serves from this pass on
        for i in sorted(self._ready_at):
            if pool.health[i] == "dead":
                del self._ready_at[i]
            elif now >= self._ready_at[i]:
                pool.lifecycle[i] = "warm"
                del self._ready_at[i]
                self.counters["promotions"] += 1
                self._log(now, "warm", i)
        load = pool.load
        if load > 0:
            self._idle_since = None
        elif self._idle_since is None:
            self._idle_since = now
        if now - self._last_decision < p.decision_interval_s:
            return
        self._last_decision = now
        alive = pool._alive()
        warm = [i for i in alive if pool.lifecycle[i] == "warm"]
        warming = [i for i in alive if pool.lifecycle[i] == "warming"]
        cold = [i for i in alive if pool.lifecycle[i] == "cold"]
        max_r = p.max_replicas if p.max_replicas is not None \
            else pool.n_replicas
        cap = sum(pool.engines[i].slots for i in warm + warming)
        # grow: pending load pressing on the serving capacity
        if (cold and len(warm) + len(warming) < max_r and load > 0
                and (cap == 0 or load > cap * p.scale_up_at)):
            self._start_warming(cold[0], now, "grow")
            return
        # scale to zero: a traffic gap outlasted idle_to_zero_s
        if (p.min_replicas == 0 and warm and load == 0
                and self._idle_since is not None
                and now - self._idle_since >= p.idle_to_zero_s):
            for i in warm:
                pool.lifecycle[i] = "cold"
                self.counters["scale_downs"] += 1
                self._log(now, "to_zero", i)
            self.counters["scale_to_zero"] += 1
            return
        # shrink: low occupancy, keep at least max(min_replicas, 1) warm
        floor = max(p.min_replicas, 1)
        idle_warm = [i for i in warm if pool.engines[i].load == 0]
        if (len(warm) > floor and idle_warm and cap > 0
                and load < cap * p.scale_down_at):
            i = idle_warm[-1]
            pool.lifecycle[i] = "cold"
            self.counters["scale_downs"] += 1
            self._log(now, "shrink", i)

    def summary(self) -> Dict[str, object]:
        return {"events": list(self.events), **self.counters}


class EnginePool:
    """R serving-engine replicas behind one engine-shaped surface."""

    # Concurrency contract, enforced statically by reprolint's
    # thread-ownership rule (tools/reprolint/README.md). During a
    # threaded step() pass, each replica's worker owns that replica's
    # state (ServingEngine declares it replica-private); everything
    # pool-level below is join-only — read or mutated only by the
    # coordinator thread, with mutations happening at/after the
    # f.result() join barrier. The pool itself runs no worker-thread
    # methods (workers execute ServingEngine.step), and step() is the
    # one method during which workers are live (_CONCURRENT_METHODS is
    # deliberately not closed over callees: _kill_replica /
    # _update_health / _hedge_from run after the join barrier).
    _THREAD_OWNERSHIP = {
        "engines": "join-only",
        "health": "join-only",
        "lifecycle": "join-only",
        "pool_stats": "join-only",
        "autoscaler": "join-only",
        "_tp": "join-only",
        "_last_progress": "join-only",
        "_stalled_passes": "join-only",
    }
    _WORKER_METHODS = ()
    _CONCURRENT_METHODS = ("step",)

    def __init__(self, engines: Sequence[ServingEngine], *,
                 threads: bool = True, failover: bool = True,
                 suspect_after: Optional[int] = None,
                 autoscale: Optional[AutoscalePolicy] = None):
        if not engines:
            raise ValueError("EnginePool needs at least one replica")
        self.engines: List[ServingEngine] = list(engines)
        self.threads = threads
        self.failover = failover
        self.suspect_after = suspect_after
        self.health: List[str] = ["healthy"] * len(self.engines)
        # lifecycle (warm/warming/cold) is orthogonal to health; without
        # an autoscaler every replica is permanently warm
        self.lifecycle: List[str] = ["warm"] * len(self.engines)
        self.autoscaler: Optional[Autoscaler] = None
        if autoscale is not None:
            self.arm_autoscale(autoscale)
        self._tp: Optional[ThreadPoolExecutor] = None
        self._last_progress = [-1] * len(self.engines)
        self._stalled_passes = [0] * len(self.engines)
        self.pool_stats: Dict[str, object] = {
            "pump_passes": 0,
            "submitted": [0] * len(self.engines),
            "deaths": 0,
            "failovers": 0,
            "suspects": 0,
            "hedges": 0,
            "replica_errors": [],
        }

    # ---- constructors --------------------------------------------------
    @classmethod
    def replicate(cls, cfg, params, *, replicas: int, seed: int = 0,
                  threads: bool = True, failover: bool = True,
                  suspect_after: Optional[int] = None,
                  autoscale: Optional[AutoscalePolicy] = None,
                  **engine_kw) -> "EnginePool":
        """R fresh replicas sharing one params pytree. Replica i samples
        with ``seed + i`` so replica 0 matches a lone engine built with
        ``seed`` (the R=1 bit-identity guarantee)."""
        if replicas < 1:
            raise ValueError("replicas must be >= 1")
        return cls([ServingEngine(cfg, params, seed=seed + i, **engine_kw)
                    for i in range(replicas)], threads=threads,
                   failover=failover, suspect_after=suspect_after,
                   autoscale=autoscale)

    @classmethod
    def like(cls, engine: ServingEngine, replicas: int, *,
             threads: bool = True) -> "EnginePool":
        """Scale an existing engine out to R replicas: the given engine
        becomes replica 0 (its queue/slots are preserved), siblings are
        clones over the same params with distinct sampling seeds."""
        if replicas < 1:
            raise ValueError("replicas must be >= 1")
        return cls([engine] + [engine.clone(seed=engine.seed + i)
                               for i in range(1, replicas)],
                   threads=threads)

    # ---- elasticity ----------------------------------------------------
    def arm_autoscale(self, policy: AutoscalePolicy, *,
                      clock: Optional[Callable[[], float]] = None
                      ) -> Autoscaler:
        """Attach an :class:`Autoscaler`: replicas beyond
        ``policy.min_replicas`` start cold and are warmed on demand."""
        self.autoscaler = Autoscaler(self, policy, clock=clock)
        return self.autoscaler

    # ---- occupancy -----------------------------------------------------
    @property
    def n_replicas(self) -> int:
        return len(self.engines)

    def _alive(self) -> List[int]:
        return [i for i in range(len(self.engines))
                if self.health[i] != "dead"]

    def _eligible(self) -> List[int]:
        """Replicas that can accept/serve work now-or-soon: alive and not
        scaled down (warming counts — queued work waits out the cold
        start there)."""
        return [i for i in self._alive() if self.lifecycle[i] != "cold"]

    @property
    def capacity(self) -> int:
        """Total KV slots across replicas (replicas × slots when uniform)
        — what ``JAXExecutor`` derives its dispatch concurrency from.
        Dead replicas contribute nothing."""
        return sum(self.engines[i].slots for i in self._alive())

    @property
    def n_active(self) -> int:
        return sum(e.n_active for e in self.engines)

    @property
    def load(self) -> int:
        return sum(e.load for e in self.engines)

    @property
    def has_work(self) -> bool:
        return any(self.engines[i].has_work for i in self._alive())

    @property
    def all_saturated(self) -> bool:
        """True when no surviving replica has a free slot left (spill
        eligibility: cloud→edge spill must not fire while any live
        replica could still admit the request)."""
        return all(self.engines[i].load >= self.engines[i].slots
                   for i in self._alive())

    def occupancy(self) -> List[Dict[str, int]]:
        """Per-replica slot-lease snapshot."""
        return [{"replica": i, "slots": e.slots, "active": e.n_active,
                 "queued": len(e.queue),
                 "free": max(e.slots - e.load, 0),
                 "requests": e.stats["requests"],
                 "slot_reuses": e.stats["slot_reuses"],
                 "peak_active": e.stats["peak_active"],
                 "health": self.health[i],
                 "lifecycle": self.lifecycle[i]}
                for i, e in enumerate(self.engines)]

    # gauges describe one replica's high-water mark, not fleet volume:
    # summing them would report a concurrency that may never have existed
    _MAX_STATS = ("peak_active", "prefill_batch_max")

    @property
    def stats(self) -> Dict[str, object]:
        """Engine-shaped aggregate of every replica's counters: volumes
        sum, per-replica gauges take the max (per-replica values are in
        ``occupancy()``)."""
        agg: Dict[str, object] = {}
        for e in self.engines:
            for k, v in e.stats.items():
                if not isinstance(v, (int, float)):
                    if v is not None:
                        agg[k] = v
                elif k in self._MAX_STATS:
                    agg[k] = max(agg.get(k, 0), v)
                else:
                    agg[k] = agg.get(k, 0) + v
        agg.setdefault("prefill_backend", None)
        agg["replicas"] = self.n_replicas
        agg["pump_passes"] = self.pool_stats["pump_passes"]
        agg["deaths"] = self.pool_stats["deaths"]
        agg["failovers"] = self.pool_stats["failovers"]
        agg["suspects"] = self.pool_stats["suspects"]
        agg["hedges"] = self.pool_stats["hedges"]
        agg["replica_health"] = list(self.health)
        if self.autoscaler is not None:
            agg["replica_lifecycle"] = list(self.lifecycle)
            agg["autoscale"] = self.autoscaler.summary()
        return agg

    # ---- engine surface ------------------------------------------------
    def saturated(self) -> bool:
        """EngineLike surface: live occupancy says no replica can admit
        another request (spill eligibility; see ``all_saturated``)."""
        return self.all_saturated

    def submit(self, prompt, *, prefix_hint=None, **kw) -> Request:
        """Enqueue on the least-loaded surviving replica (healthy
        replicas beat suspect ones, warm replicas beat warming on equal
        load; then prefix affinity, then lowest index). An elastic pool
        with nothing warm is poked first — the first arrival after a gap
        starts a cold replica warming and queues on it.

        Prefix affinity: each replica keeps its own prefix index (it
        lives on the engine, so a dead replica's index dies with it and
        failed-over requests simply re-match on the survivor). Among
        equally loaded candidates the one holding the longest cached
        prefix of ``prefix_hint`` (the scheduler's DAG hint) — or of the
        prompt itself — wins, so co-scheduled siblings land where their
        shared context is already hot. Affinity never outranks load:
        reuse saves prefill, not decode, so piling onto a hot replica
        would trade a prefill skip for whole decode steps."""
        alive = self._alive()
        if not alive:
            raise RuntimeError("EnginePool.submit: all replicas are dead")
        if self.autoscaler is not None:
            self.autoscaler.tick()
            cands = self._eligible()
            if not cands:
                self.autoscaler.poke()
                cands = self._eligible()
            cands = cands or alive
        else:
            cands = alive
        match = {j: 0 for j in cands}
        if len(cands) > 1 and any(
                getattr(self.engines[j], "prefix_reuse", False)
                for j in cands):
            ids = prefix_hint
            if ids is None and isinstance(prompt, str):
                from repro.data import tokenizer as tok
                ids = tok.encode(prompt)
            elif ids is None:
                ids = list(prompt)
            for j in cands:
                fn = getattr(self.engines[j], "prefix_match_len", None)
                match[j] = fn(ids) if fn is not None else 0
        i = min(cands, key=lambda j: (self.health[j] != "healthy",
                                      self.engines[j].load,
                                      self.lifecycle[j] != "warm",
                                      -match[j], j))
        self.pool_stats["submitted"][i] += 1
        return self.engines[i].submit(prompt, **kw)

    # reprolint: hot
    def step(self) -> List[Request]:
        """One pool pass: step every surviving replica with pending work
        (see the module docstring for the threaded vs
        launch-all/commit-all pass shapes); for a single loaded replica
        this is exactly ``ServingEngine.step``. A replica that raises —
        from its worker thread, the fast path, or any sequential phase —
        is handed to ``_kill_replica`` *after* every sibling's results
        are joined, so one crash never loses another replica's finished
        requests or deadlocks the join. Only *warm* replicas step —
        warming replicas hold their queues until the autoscaler promotes
        them (every replica is warm when no autoscaler is armed)."""
        with span("pool.step"):
            if self.autoscaler is not None:
                self.autoscaler.tick()
            loaded = [(i, self.engines[i]) for i in self._alive()
                      if self.engines[i].has_work
                      and self.lifecycle[i] == "warm"]
            if not loaded:
                return []
            self.pool_stats["pump_passes"] += 1
            finished: List[Request] = []
            errors: List[Tuple[int, BaseException]] = []
            if len(loaded) == 1:
                i, e = loaded[0]
                try:
                    finished = e.step()
                except Exception as exc:
                    errors.append((i, exc))
            elif self.threads:
                if self._tp is None:
                    self._tp = ThreadPoolExecutor(
                        max_workers=len(self.engines),
                        thread_name_prefix="enginepool")
                # one worker per loaded replica: replica state is thread-
                # private, results join in replica-index order (determinism)
                futs = [(i, self._tp.submit(e.step)) for i, e in loaded]
                for i, f in futs:
                    try:
                        finished.extend(f.result())
                    except Exception as exc:
                        errors.append((i, exc))
            else:
                # launch-all/commit-all: a replica that raises in any phase
                # drops out of the later phases of this pass
                live = []
                for i, e in loaded:
                    try:
                        e._admit()
                        live.append((i, e))
                    except Exception as exc:
                        errors.append((i, exc))
                prefills = []
                for i, e in live:
                    try:
                        prefills.append((i, e, e._prefill_launch()))
                    except Exception as exc:
                        errors.append((i, exc))
                live = []
                for i, e, p in prefills:
                    try:
                        if p is not None:
                            e._prefill_commit(p)
                        live.append((i, e))
                    except Exception as exc:
                        errors.append((i, exc))
                decodes = []
                for i, e in live:
                    try:
                        decodes.append((i, e, e._decode_launch()))
                    except Exception as exc:
                        errors.append((i, exc))
                for i, e, d in decodes:
                    try:
                        if d is not None:
                            finished.extend(e._decode_commit(d))
                    except Exception as exc:
                        errors.append((i, exc))
            for i, exc in errors:
                self._kill_replica(i, exc)
            self._update_health()
            return finished

    # ---- failure handling ----------------------------------------------
    def _kill_replica(self, i: int, exc: BaseException) -> None:
        """Mark replica ``i`` dead and fail its work over to survivors
        (active slots in slot order, then queue FIFO — deterministic).
        Failed-over requests restart from the prompt: their generation
        state lived in the dead replica's KV slots. With
        ``failover=False`` the captured exception surfaces instead."""
        self.health[i] = "dead"
        self.pool_stats["replica_errors"].append(
            f"replica {i}: {type(exc).__name__}: {exc}")
        if not self.failover:
            raise RuntimeError(
                f"replica {i} step failed (failover disabled)") from exc
        self.pool_stats["deaths"] += 1
        dead = self.engines[i]
        orphans = [r for r in dead.active if r is not None and not r.done]
        orphans.extend(dead.queue)
        for r in orphans:
            dead.cancel(r)
        alive = self._alive()
        if orphans and not alive:
            raise RuntimeError(
                f"all {len(self.engines)} replicas dead with "
                f"{len(orphans)} requests stranded") from exc
        # failover lands on serving replicas; if the survivors are all
        # scaled down, poke one awake rather than stranding work cold
        targets = self._eligible()
        if orphans and not targets and self.autoscaler is not None:
            self.autoscaler.poke()
            targets = self._eligible()
        targets = targets or alive
        for r in orphans:
            j = min(targets, key=lambda j_: (self.health[j_] != "healthy",
                                             self.engines[j_].load,
                                             self.lifecycle[j_] != "warm",
                                             j_))
            r.output_ids.clear()
            r.done = False
            r._engine = self.engines[j]
            self.engines[j].queue.append(r)
            self.pool_stats["failovers"] += 1
            self.pool_stats["submitted"][j] += 1

    def _update_health(self) -> None:
        """Straggler detection (armed by ``suspect_after``): a replica
        holding work that makes no counter progress for N consecutive
        passes turns suspect and its work is hedged away; first progress
        afterwards restores it to healthy."""
        if self.suspect_after is None:
            return
        for i, e in enumerate(self.engines):
            if self.health[i] == "dead":
                continue
            prog = (e.stats["tokens_out"] + e.stats["prefill_tokens"]
                    + e.stats["requests"])
            if prog != self._last_progress[i]:
                self._last_progress[i] = prog
                self._stalled_passes[i] = 0
                if self.health[i] == "suspect":
                    self.health[i] = "healthy"
            elif e.has_work and self.lifecycle[i] == "warm":
                self._stalled_passes[i] += 1
                if (self._stalled_passes[i] >= self.suspect_after
                        and self.health[i] == "healthy"):
                    self.health[i] = "suspect"
                    self.pool_stats["suspects"] += 1
                    self._hedge_from(i)

    def _hedge_from(self, i: int) -> None:
        """Hedged re-dispatch: move a suspect replica's pending work onto
        strictly-healthy replicas (restarted from the prompt). The
        suspect keeps nothing but stays eligible to recover; with no
        healthy replica left the work stays put."""
        healthy = [j for j in range(len(self.engines))
                   if self.health[j] == "healthy"
                   and self.lifecycle[j] == "warm"]
        if not healthy:
            return
        src = self.engines[i]
        moved = [r for r in src.active if r is not None and not r.done]
        moved.extend(src.queue)
        for r in moved:
            src.cancel(r)
            j = min(healthy, key=lambda j_: (self.engines[j_].load, j_))
            r.output_ids.clear()
            r.done = False
            r._engine = self.engines[j]
            self.engines[j].queue.append(r)
            self.pool_stats["hedges"] += 1
            self.pool_stats["submitted"][j] += 1

    def cancel(self, req: Request) -> bool:
        """Cancel a pool-owned request wherever it currently lives."""
        owner = getattr(req, "_engine", None)
        for e in self.engines:
            if owner is e:
                return e.cancel(req)
        return False

    # reprolint: hot
    def pump(self) -> bool:
        """Advance every replica with pending work one step, in one
        pass. Returns whether anything progressed. Elastic pools tick
        their autoscaler even on empty passes — that is what lets a pool
        scale to zero during a traffic gap and promote warming replicas
        on wall-clock time."""
        if self.autoscaler is not None:
            self.autoscaler.tick()
        if not self.has_work:
            return False
        self.step()
        return True

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        done: List[Request] = []
        for _ in range(max_steps):
            if not self.has_work:
                break
            done.extend(self.step())
        return done

    def run_until(self, req: Request, max_steps: int = 10_000) -> Request:
        """Step the pool until ``req`` finishes; co-resident requests on
        every replica keep advancing on the same passes."""
        owner = getattr(req, "_engine", None)
        if not any(owner is e for e in self.engines):
            raise ValueError(
                f"request {req.rid} was never submitted to this pool "
                f"(submit() returns the Request object to wait on)")
        for _ in range(max_steps):
            if req.done:
                return req
            # re-resolve ownership every pass: failover/hedging may have
            # moved the request to another replica mid-wait
            owner = getattr(req, "_engine", None)
            if not any(owner is e for e in self.engines):
                raise RuntimeError(
                    f"request {req.rid} lost its replica mid-run "
                    f"(cancelled without failover?)")
            if not owner.has_work:
                raise RuntimeError(
                    f"replica drained with request {req.rid} unfinished "
                    f"(engine bug: an owned request left the queue)")
            self.step()
        if req.done:
            return req
        raise RuntimeError(f"request {req.rid} did not finish "
                           f"within {max_steps} pool passes")
