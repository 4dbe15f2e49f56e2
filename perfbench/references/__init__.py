"""Plain references, one per configuration: straightforward jax.numpy in
float32, no kernels, cache or batching; nothing of the program imported."""
