"""Plain float32 references: straightforward ``jax.numpy``, no kernels,
cache or batching tricks, ``highest`` matmul precision.  They import
nothing of the program and take weights only from the benchmark's own
seeded generator (``harness.weights``)."""
