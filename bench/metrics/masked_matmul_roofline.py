"""masked_matmul_roofline: the FedAP masked FFN kernel's share of its
roofline in training.

For every masked matmul the traced rounds and evals require (the up and
gate projections of every layer: forward, and for every gradient step
the input and weight gradients), the least time the chip could take:
the larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth, counted at bfloat16 over the kept FFN units only (the pruned
units are no work).  Their sum over the device time of the trace's
``masked_matmul_fwd``, ``_dx`` and ``_dw`` events.  A forward recomputed
for the backward pass is no required work but its time counts.  When
the trace holds another number of gradient kernels than the traced
rounds call for, the share is not reported.  Moves ``round_s``.
"""


def _t(flops, byts, peaks):
    return max(flops / peaks["flops_bf16"], byts / peaks["hbm_bytes_per_s"])


def _pass(rows, d, ff, peaks, grad):
    """Least seconds of one layer's two masked matmuls over ``rows``."""
    fwd = _t(2 * rows * d * ff, 2 * (rows * d + d * ff + rows * ff), peaks)
    if not grad:
        return 2 * fwd
    dx = _t(2 * rows * ff * d, 2 * (rows * ff + d * ff + rows * d), peaks)
    dw = _t(2 * rows * d * ff, 2 * (rows * d + rows * ff + d * ff), peaks)
    return 2 * (fwd + dx + dw)


def read(layer):
    if not layer or "hp" not in layer:
        return None
    red, dm, hp, peaks = (layer["reduced"], layer["dims"], layer["hp"],
                          layer["peaks"])
    ev = {k: red.ops(f"masked_matmul_{k}") for k in ("fwd", "dx", "dw")}
    if not all(ev.values()):
        return None
    R, E, L = layer["rounds"], layer["evals"], dm["L"]
    C, steps, tau = hp["clients_per_round"], hp["local_steps"], hp["tau"]
    grads_batched = R * (steps + tau) * L * 2
    grads_each = R * (C * steps + tau) * L * 2
    n_grad = len(ev["dx"])
    if n_grad != len(ev["dw"]) or n_grad not in (grads_batched, grads_each):
        return None
    if len(ev["fwd"]) < n_grad + E * L * 2:
        return None
    if not layer.get("kept"):
        return None
    S, d, ff = hp["row_shape"][0], dm["d"], layer["kept"]["mlp"].shape[1]
    least = (R * L * (C * steps * _pass(hp["batch_size"] * S, d, ff, peaks,
                                        True)
                      + tau * _pass(hp["server_batch_size"] * S, d, ff,
                                    peaks, True))
             + E * L * _pass(hp["test_rows"] * S, d, ff, peaks, False))
    device = sum(r[2] for k in ev for r in ev[k]) * 1e-9
    if device <= 0:
        return None
    return 100.0 * least / device
