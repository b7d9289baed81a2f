"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel subpackage is ``kernel.py`` (pl.pallas_call + BlockSpec VMEM
tiling), ``ops.py`` (padded/jit public wrapper), ``ref.py`` (pure-jnp
oracle).  Whether a kernel is compiled by Mosaic or run in the Pallas
interpreter is decided here, from the platform, by :func:`use_interpreter`.

  stencil/          2D image/Jacobi stencil (paper §6.4 StencilEngine hotspot)
  flash_attention/  causal GQA flash attention (LM prefill/train hotspot)
  ssd_scan/         Mamba2 SSD chunked scan (mamba2 / zamba2 archs)
  mandelbrot/       escape-time fractal (paper §6.6 farm workload)
  moe_gmm/          grouped expert matmul (MoE archs)
"""

import jax


def use_interpreter() -> bool:
    """True when JAX's default backend is the CPU, which has no Mosaic
    compiler: the kernels then run in the Pallas interpreter.  On a TPU they
    are always compiled; there is no option to interpret them there."""
    return jax.default_backend() == "cpu"
