"""decode_mfu: the decode waves' share of the chip's bf16 peak.

The FLOPs of the traced waves' active slot-steps (a slot whose request is
still running at that step: one token through every layer at its own
context, the kept FFN units only, and the output head), over the waves'
wall time (the ``wave`` spans) times the peak.  Moves ``tpot_p95_ms``.
"""
from bench import lm_math


def read(layer):
    if not layer or "contexts" not in layer:
        return None
    dm, ff = layer["dims"], layer["ff_kept"]
    flops = sum(lm_math.token_flops(dm, c, ff)
                for wave in layer["contexts"] for step in wave for c in step)
    spans = [s for s in layer["reduced"].spans if s[0] == "wave"]
    wall = sum(s[2] for s in spans) * 1e-9
    if wall <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (wall * layer["peaks"]["flops_bf16"])
