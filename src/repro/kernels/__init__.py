"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
wrapped by ops.py (jit, padding, dataflow selection, platform dispatch) and
pinned to ref.py (pure-jnp oracle) by tests/test_kernels_*.py in interpret
mode on the CPU; tests/test_tpu_compile.py compiles them for a described
TPU v5e.  On a TPU the serving engine runs the paged decode and paged
prefill/verify kernels compiled; its quantized projections run the XLA
dequant reference (``ref.py::mpmm_ref``), not the ``mpmm`` kernel.
"""
