"""device_idle.serve: the share of the traced serving window (first to
last traced wave) in which no operation ran on the device.  Moves
``tpot_p95_ms``.
"""


def read(layer):
    if not layer or "contexts" not in layer:
        return None
    red = layer["reduced"]
    if red.window_s <= 0 or not red.device:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
