"""Device time of the FFT Poisson solve per step, ms: the operations whose
name stack runs through ``fft_poisson`` (forward and inverse FFTs and the
spectral division)."""
import devtrace as DT


def read(ctx):
    return DT.per_step_ms(ctx, lambda o: "jit(fft_poisson)" in o.op_name)
