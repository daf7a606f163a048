"""Plain references: each architecture's forward pass in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``, reading
the program's parameter tree.  No kernels, no cache, no batching tricks,
and no import from the program."""
