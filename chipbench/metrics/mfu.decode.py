"""Model FLOP utilization of the decode program: the operations that the
decode rows of the traced window need (the family's ``decode_flops`` at
each row's context), per execution of ``jit_decode_fn``, over that
program's device time per execution, as a share of the chip's bf16
peak. Nothing where the family counts no FLOPs or nothing was decoded."""


def read(r):
    flops_of = getattr(r.cell.family, "decode_flops", None)
    if r.reduced is None or flops_of is None or r.ctx.trace_host is None:
        return None
    dev, n = r.reduced.program_time("jit_decode_fn")
    max_len = int(r.cell.cell["deployment"]["max_len"])
    lo, hi = r.ctx.trace_host
    flops, steps = 0.0, set()
    for tr in r.recorder.tracked():
        # a request's first token comes from prefill; token j after it
        # was decoded attending the context and the j tokens before it
        ctx0 = min(len(tr.req.prompt_ids),
                   max_len - tr.req.max_new_tokens - 1)
        for j, t in enumerate(tr.times[1:], 1):
            if lo <= t <= hi:
                flops += flops_of(r.sizes, ctx0 + j)
                steps.add(t)
    if not (n and dev > 0 and steps):
        return None
    return 100.0 * (flops / len(steps)) / (dev / n) / r.peaks["flops_bf16"]
