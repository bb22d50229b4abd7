"""Share of the latent pool that decode reads: the engine's
``latent_lines_read`` over ``latent_lines_stored`` (each row that serves
a request, its lines up to its position in whole blocks of the TPU
decode kernel, over slots x lines of the pool; per decode step, summed
over the whole run: warm-up, window and drain). Nothing where the program
keeps no such counter (a dense cell, or a program before the kernel)."""


def read(r):
    c = r.result.get("counters", {}).get("engine", {})
    stored = c.get("latent_lines_stored")
    if not stored:
        return None
    return 100.0 * c["latent_lines_read"] / stored
