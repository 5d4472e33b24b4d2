"""mx.rtc — runtime kernel compilation.

Reference: python/mxnet/rtc.py (CudaModule over NVRTC,
include/mxnet/rtc.h:39).

TPU-native: the CUDA-source path cannot exist on TPU; the runtime
kernel facility here is **Pallas** — `PallasModule` compiles a Pallas
kernel function at runtime, the direct analog of CudaModule compiling
a CUDA C string.  CudaModule is kept as a clear error for API parity.
"""

from __future__ import annotations

from .base import MXNetError
from .ndarray import NDArray
from .util import pallas_interpret

__all__ = ["CudaModule", "PallasModule"]


class CudaModule:
    """Unavailable on TPU (reference: rtc.py CudaModule)."""

    def __init__(self, source, options=(), exports=()):
        raise MXNetError(
            "CudaModule (NVRTC) is not available on TPU. Use "
            "mx.rtc.PallasModule to JIT-compile a Pallas TPU kernel at "
            "runtime instead.")


class PallasModule:
    """Compile Pallas kernels at runtime — the TPU analog of NVRTC.

    kernel_fn: a function written with jax.experimental.pallas (pl.*)
    taking Refs; get_kernel returns a launcher with CudaModule-like
    call semantics.
    """

    def __init__(self, kernel_fn, out_shape_fn, grid=None):
        self._kernel_fn = kernel_fn
        self._out_shape_fn = out_shape_fn
        self._grid = grid

    def get_kernel(self, name=None, signature=None):
        kernel_fn = self._kernel_fn
        out_shape_fn = self._out_shape_fn
        grid = self._grid

        class _Launcher:
            def launch(self, args, ctx=None, grid_dims=None, block_dims=None,
                       shared_mem=0):
                from jax.experimental import pallas as pl

                arrays = [a.data_jax if isinstance(a, NDArray) else a
                          for a in args]
                out_shape = out_shape_fn(*arrays)
                kw = {}
                if grid_dims is not None or grid is not None:
                    # gridless kernels must OMIT the arg: pallas_call
                    # rejects an explicit grid=None
                    kw["grid"] = grid_dims if grid_dims is not None else grid
                fn = pl.pallas_call(kernel_fn, out_shape=out_shape,
                                    interpret=pallas_interpret(), **kw)
                res = fn(*arrays)
                return NDArray(res)

            __call__ = launch

        return _Launcher()
