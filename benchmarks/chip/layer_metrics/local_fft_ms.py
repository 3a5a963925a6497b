"""local_fft_ms: device time per call of the sharded pipeline's stage DFTs,
the XLA einsum passes on each side of the all-to-all (scope ``fft.local``),
averaged over chips (cells on several chips)."""
from benchmarks.chip import mesh_scopes


def read(run):
    return mesh_scopes.scope_ms(run, "fft.local")
