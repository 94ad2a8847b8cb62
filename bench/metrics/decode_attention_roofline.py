"""decode_attention_roofline: the flash-decode kernel's share of its
roofline.

For each decode step of the traced waves and each layer, the least time
the chip could take for the attention the running slots require: q
against each slot's valid K/V rows (its own context, from the harness's
admission bookkeeping) and the output, in bfloat16 -- the larger of the
FLOPs over the bf16 peak and the bytes over the HBM bandwidth.  Their sum
over the device time of the trace's ``decode_attention`` events.  Slots
with no running request are no work.  When the trace holds another number
of these events than steps times layers, the share is not reported.
Moves ``tpot_p95_ms``.
"""
from bench import lm_math


def read(layer):
    if not layer or "contexts" not in layer:
        return None
    red, dm, peaks = layer["reduced"], layer["dims"], layer["peaks"]
    ev = red.ops("decode_attention")
    steps = [s for wave in layer["contexts"] for s in wave]
    if not ev or len(ev) != len(steps) * dm["L"]:
        return None
    least = 0.0
    for ctx in steps:
        f, b = lm_math.decode_attention_work(dm, ctx)
        least += dm["L"] * max(f / peaks["flops_bf16"],
                               b / peaks["hbm_bytes_per_s"])
    device = sum(r[2] for r in ev) * 1e-9
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device
