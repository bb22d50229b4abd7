"""Step programs the engines compiled or loaded inside the window: the
change in ``jit_retraces`` (per shared step-program set) plus
``prefix_seed_compiles`` between the window's opening and its close."""


def read(r):
    return float(r.ctx.compiles_in_window())
