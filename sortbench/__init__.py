"""sortbench: the on-chip benchmark of ``mpitest_tpu.sort``.

Driven by data: ``BENCHMARK.json`` at the checkout's root names the
cells, and every configuration, traffic mix and metric is found by its
name under this directory (see :mod:`sortbench.spec`).  The yardstick
lives here — generators, the plain reference, the trace reduction, the
table of peaks — and imports nothing of the program but its entry
point, ``mpitest_tpu.sort``, and its tracer.
"""
