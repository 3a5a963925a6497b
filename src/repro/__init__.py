"""repro: TurboFFT-on-TPU — a fault-tolerant FFT library and serving runtime.

FP64 (complex128) support is a first-class paper feature (the paper evaluates
both FP32 and FP64), so x64 is enabled globally. Code that wants float32 or
complex64 names the dtype explicitly and is unaffected.
"""
import jax

jax.config.update("jax_enable_x64", True)

__version__ = "1.0.0"
