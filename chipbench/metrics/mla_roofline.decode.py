"""Share of the HBM roofline reached by the latent attention of the
decode program: the bytes one decode step's attention needs (the latent
lines its rows attend at their live positions, 1,152 B a line in each of
the 27 layers, plus every layer's attention weights; the family's
``mla_decode_bytes``) over the chip's HBM bandwidth, over the device time
per ``jit_decode_fn`` execution of the ops traced under the ``mla``
scope. Counted from the arithmetic, not from what the program reads, so
a program that skips dead lines reads higher. Nothing where the family
has no such scope or nothing was decoded in the trace."""


def read(r):
    fam = r.cell.family
    if not hasattr(fam, "scope_roofline"):
        return None
    return fam.scope_roofline(
        r, "mla", lambda r, lines, steps: fam.mla_decode_bytes(
            r.sizes, lines / steps))
