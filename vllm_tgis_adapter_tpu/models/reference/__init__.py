"""Plain references: a model family's forward pass in straightforward
float32 ``jax.numpy``, with no cache, kernel, chunk or batch, for the
served path to be held to (tests/ at a small size; ``benchmark/`` keeps
its own numpy copy for the chip, independent of the package)."""
