"""layout_ms: device time per call of the relayouts into and out of the
kernel's ``(B*R, 128)`` blocks, the batch padding and slicing and the
multi-pass transposes (scope ``fft.layout``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "fft.layout")
