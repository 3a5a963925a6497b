"""split_join_ms: device time per call of the conversions between the
complex operand and the kernel's float planes (scopes ``fft.split`` and
``fft.join``, with the copies the compiler puts on their results)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "fft.split", "fft.join")
