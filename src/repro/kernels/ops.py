"""jit'd public wrappers for all Pallas kernels (the drop-in API).

Each wrapper decides when it is called: on the CPU backend the kernel runs
in Pallas interpret mode (correctness validation), on any other platform
it is compiled for the device.  ``tests/test_tpu_compile.py`` compiles
every kernel for a described v5e chip at real widths.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref  # noqa: F401  (oracles live here)
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mamba_scan import mamba_chunk_scan_chunked as _mamba
from repro.kernels.mlstm import mlstm_chunk_scan as _mlstm
from repro.kernels.moe_gmm import moe_gmm as _gmm
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


def _platform_wrapper(kernel, static_argnames=()):
    jitted = jax.jit(kernel, static_argnames=(*static_argnames, "interpret"))

    @functools.wraps(kernel)
    def call(*args, **kwargs):
        return jitted(*args, interpret=jax.default_backend() == "cpu",
                      **kwargs)
    return call


flash_attention = _platform_wrapper(
    _flash, ("causal", "window", "scale", "blk_q", "blk_k"))
decode_attention = _platform_wrapper(_decode, ("scale", "blk_w"))
rmsnorm = _platform_wrapper(_rmsnorm, ("eps", "blk"))
moe_gmm = _platform_wrapper(_gmm, ("blk_c", "blk_f", "blk_d"))
mamba_chunk_scan = _platform_wrapper(_mamba)
mlstm_chunk_scan = _platform_wrapper(_mlstm)
