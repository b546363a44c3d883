"""Pallas TPU kernels.

  grouped_scatter/   the paper's technique as a kernel: conflict-group
                     segment reduction as a blocked one-hot MXU matmul

The subpackage ships kernel.py (pl.pallas_call + BlockSpec), ops.py
(jitted wrapper), ref.py (pure-jnp oracle); validated in interpret mode
(tests/test_kernels.py shape/dtype sweeps).
"""
