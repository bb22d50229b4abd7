"""Host spans on the profiler's clock.

``span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation`` named
``hf.<name>``. While a profiler trace is being taken the span lands in
the trace's host plane, on the thread that opened it and on the device
trace's clock, so device idle time can be put down to the host phase
around it; otherwise it costs under a microsecond. There is no switch
and no exporter. Pass ids (``qid``, ``sid``) only on per-subtask spans,
never on per-pass or per-step ones.

Spans, by layer:

* scheduler (``core/scheduler.py``): ``sched.pass`` (one pump-loop
  iteration), ``sched.poll``, ``sched.complete``, ``sched.route`` (one
  policy decision), ``sched.dispatch``, ``sched.nap`` (a sleep waiting
  for an arrival or a backoff);
* executor (``serving/engine.py``): ``exec.submit`` (prompt build,
  tokenization, engine submit of one subtask);
* pool (``serving/pool.py``): ``pool.step`` (one pass, the join on the
  replica workers included);
* engine (``serving/engine.py``): ``engine.step``, ``engine.admit``,
  ``engine.prefill_launch``, ``engine.decode_launch``, ``engine.sync``
  (the blocking device-to-host read of sampled ids).

Device scopes: the latent-attention stack (``models/transformer.py``
``latent_*``, deepseek-v2-lite) traces each layer's attention under
``jax.named_scope("mla")`` and its expert layer (router, held experts,
shared expert) under ``jax.named_scope("moe")``, so the step programs'
device ops carry the scope in their compiled ``op_name`` metadata.

Counters: ``ServingEngine.stats`` (beside token, step and prefix
counts) holds, for a dropless expert stack, ``expert_picks_<i>`` (the
(token, expert) pairs held expert ``i`` computed in decode) and
``experts_hit`` (held experts that computed any, per layer and step),
summed over decode steps and layers, over the rows that serve a request
(parked slots and slots still prefilling are computed but not counted).
They cover the engine's whole life, warm-up and drain included. The
decode program returns them with the sampled ids; the engine reads both
in its one sync.
"""
from __future__ import annotations

import jax

PREFIX = "hf."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``hf.<name>``, used as ``with span(...):``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)
