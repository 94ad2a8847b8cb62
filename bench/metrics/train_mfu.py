"""train_mfu: the whole training step's share of the chip's bf16 peak.

The FLOPs the traced rounds and evals require (forward and backward of
every client and server row, forward of every eval row; the kept units
only under FedAP masks; no recomputation), as the configuration's file
counts them (``train_flops``), over the traced window times the peak.
Moves ``round_s``.
"""


def read(layer):
    if not layer or "flops" not in layer:
        return None
    flops = (layer["rounds"] * layer["flops"]["round"]
             + layer["evals"] * layer["flops"]["eval"])
    window = layer["reduced"].window_s
    if window <= 0:
        return None
    return 100.0 * flops / (window * layer["peaks"]["flops_bf16"])
