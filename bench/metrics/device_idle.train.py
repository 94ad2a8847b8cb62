"""device_idle.train: the share of the traced training window in which no
operation ran on the device (1 minus the union of the device operations'
intervals over the window).  Moves ``round_s``.
"""


def read(layer):
    if not layer or "hp" not in layer:
        return None
    red = layer["reduced"]
    if red.window_s <= 0 or not red.device:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
