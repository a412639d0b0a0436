"""Plain references: each architecture's forward pass in straightforward
``jax.numpy`` and float32, with no kernel, cache or batching."""
