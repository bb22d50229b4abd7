"""Pallas TPU kernels for the serving/training hot paths.

Five kernels, each with a pure-jnp oracle (``ref.py``) it is allclose-
validated against in interpret mode on CPU:

* ``flash_attention``          — online-softmax prefill/training attention
  (causal, sliding window, GQA, scalar ``q_offset``), grid (B,H,Sq/BQ,Sk/BK).
* ``ragged_prefill_attention`` — batched ragged chunked-prefill attention
  for the slot-pooled serving cache: per-row ``pos0``/``take`` in scalar-
  prefetch SMEM, KV bounded to the engine's ``kv_width`` bucket, fully
  masked blocks skipped.
* ``batched_decode_attention`` — one launch per decode tick: every slot's
  whole GQA head stack in a (B, M/BK) grid with per-slot ``kv_len`` in
  SMEM; this is what the engine dispatches.
* ``decode_attention``         — the original per-head flash-decode kernel,
  kept as the simple reference shape for roofline comparisons.
* ``chunked_gla``              — chunked gated-linear-attention scan for the
  Mamba2/mLSTM recurrence.

(plus ``rmsnorm``, a small VPU warm-up kernel.)

Block-size / grid tuning. The CI container runs the kernels in interpret
mode, where every grid step lowers to its own chain of XLA ops — so the
dominant cost is the *number of grid steps*, not arithmetic. The two
serving kernels are therefore shaped to minimise launches:

* All H query heads are folded into each block and the GQA groups are
  reshaped ``[H, bq, hd] -> [KV, grp*bq, hd]`` so the score and
  weighted-value contractions are single KV-batched ``dot_general`` calls
  per block instead of a per-head loop — this removed the H multiplier
  from the grid (the prefill grid is (G, Sq/BQ, W/BK); decode is
  (B, M/BK)).
* Defaults ``bq=128`` / ``bk=256`` make one engine prefill chunk a single
  q block and halve the KV walk relative to square 128-blocks; on the
  serving microbench shapes this is the difference between the Pallas
  path losing ~3x to the fused-einsum reference and beating it
  (see ``benchmarks/serve_throughput.py`` prefill/decode microbenches,
  gated by ``benchmarks/check_bench.py``).
* The KV walk is the innermost "arbitrary" grid dimension while the q
  block's index map stays fixed across it, so Mosaic's pipeliner keeps q
  resident in VMEM and double-buffers the next KV block's copy against
  the current block's compute (on real TPUs; interpret mode simply skips
  revisited copies).
* Fully-masked blocks are skipped with ``pl.when`` (a real ``lax.cond``
  at runtime, not just a mask) — verified to actually fire on serving
  traces by NaN-poisoning dead KV in
  ``tests/test_ragged_prefill_kernel.py::test_masked_block_skip_fires``.

Dispatch contract: model code never imports kernels directly — it calls
``layers._dispatch_attention`` / ``layers.ragged_prefill_attention``,
which route to the jit'd wrappers in ``ops.py`` when
``dispatch.use_pallas()`` is on (REPRO_USE_PALLAS=1 or
``pallas_enabled(True)``) and to the jnp reference otherwise. The
wrappers own layout transposes ([B,S,H,hd] model layout -> [B,H,S,hd]
blocked layout), GQA head mapping and padding to block multiples. Every
kernel entry point takes ``interpret=None`` by default, which follows
``dispatch.interpret_mode()``: CPU interprets, a TPU compiles. Decode
sampling is fused at the XLA level: ``engine._jit_steps`` jits the
attention output straight into ``_device_sample`` so the sampled token
ids are the only per-tick host transfer.
"""
