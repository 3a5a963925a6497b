"""abft_decode_ms: device time per call of the ABFT checksum decode
(checksum FFT, detection, location: scope ``abft.decode``) and the online
correction (``abft.correct``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms(run, "abft.decode", "abft.correct")
