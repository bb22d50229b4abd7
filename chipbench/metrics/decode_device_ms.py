"""Device time of one execution of the engine's decode program
(``jit_decode_fn`` in the trace's XLA modules), milliseconds."""


def read(r):
    if r.reduced is None:
        return None
    tot, n = r.reduced.program_time("jit_decode_fn")
    return 1000.0 * tot / n if n else None
