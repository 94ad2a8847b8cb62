"""wave_host_ms: host time per decode wave outside the device's work.

For each traced ``wave`` span (``DecodeEngine.step_wave``: admissions,
the wave program, the done-mask sync, retirement), its length less the
time in which the device ran an operation inside it; the mean over the
traced waves, in milliseconds.  Moves ``ttft_p95_ms``.
"""
from bench import trace


def read(layer):
    if not layer or "contexts" not in layer:
        return None
    red = layer["reduced"]
    waves = [s for s in red.spans if s[0] == "wave"]
    if not waves:
        return None
    host = [s[2] - trace.covered(red.busy, s[1], s[1] + s[2]) for s in waves]
    return sum(host) / len(host) * 1e-6
