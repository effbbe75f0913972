"""Operation and byte counts of the benchmark's models and kernels, from
their shapes alone."""
