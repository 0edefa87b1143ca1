"""On-chip serving benchmark: data-driven cells run through the repo's
``Scheduler`` -> ``Engine`` -> quantized-matmul kernels.

Everything that defines the yardstick lives here and nowhere in the program:
traffic generation, weight synthesis, the float32 reference, the trace
reduction, the work and peak tables, and the metric readers.
"""
