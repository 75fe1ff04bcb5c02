"""Plain references of the architectures the benchmark runs: straightforward
``jax.numpy`` in float32, no kernels, no caches, no candidate batching.  The
benchmark keeps a byte-for-byte copy of each under ``bench/reference/``."""
