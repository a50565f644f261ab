"""Jit'd dispatch layer over the Pallas kernels.

On CPU the kernels execute in interpret mode — the kernel body runs in
Python for correctness validation. On a TPU the calls compile to Mosaic:
the engine kernels ``avg_disp`` / ``avg_disp_outer`` (and ``opt_step``,
``mix_disp``, ``compressed_mix``) run on a v5e chip and are compiled for
one in ``tests/test_tpu_compile.py``; the model kernels below have not
been compiled for a TPU yet. The model code (repro.models.*) calls these
via ``impl="pallas"``.
"""
from repro.kernels.avg_disp import avg_disp, avg_disp_outer  # noqa: F401
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.rglru_scan import rglru_scan  # noqa: F401
from repro.kernels.rwkv6_scan import rwkv6_scan  # noqa: F401
