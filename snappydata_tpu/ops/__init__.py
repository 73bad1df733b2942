"""TPU kernels (Pallas) for hot ops the XLA-level path can't express
optimally. Import from submodules. The kernels compile on an accelerator
and run in Pallas interpret mode on the CPU backend (the tests)."""

from snappydata_tpu.ops.pallas_reduce import masked_kahan_sum  # noqa: F401
