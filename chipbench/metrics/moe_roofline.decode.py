"""Share of the HBM roofline reached by the expert layers of the decode
program: the bytes one decode step's expert layers need (the held experts
it hits, 3 x 2,048 x 1,408 x 2 B each, plus every expert layer's shared
expert and router; the family's ``moe_decode_bytes``) over the chip's HBM
bandwidth, over the device time per ``jit_decode_fn`` execution of the
ops traced under the ``moe`` scope (router, the copy of each layer's held
banks, the grouped matmuls, the shared expert). Experts hit per step: the
engine's ``experts_hit`` over its decode ``steps``, a whole-run average
(warm-up, window and drain; live rows only), not a reading of the traced
window, where every slot is busy and the average lies lower. Nothing
where the program keeps no such counter or scope."""


def read(r):
    fam = r.cell.family
    c = r.result.get("counters", {}).get("engine", {})
    if not hasattr(fam, "scope_roofline") or not c.get("steps") \
            or "experts_hit" not in c:
        return None
    hit = c["experts_hit"] / c["steps"]
    return fam.scope_roofline(
        r, "moe", lambda r, lines, steps: fam.moe_decode_bytes(r.sizes, hit))
