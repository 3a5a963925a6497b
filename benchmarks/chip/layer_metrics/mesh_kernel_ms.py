"""mesh_kernel_ms: device time per call of the sharded pipeline's Pallas
block-kernel passes, the stage DFTs on each side of the all-to-all (scope
``fft.kernel``), averaged over chips (cells on several chips). A program
whose stage DFTs are not kernel passes reports nothing."""
from benchmarks.chip import mesh_scopes


def read(run):
    return mesh_scopes.scope_ms(run, "fft.kernel")
