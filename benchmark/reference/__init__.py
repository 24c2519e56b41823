"""Plain references: straightforward jax.numpy forward passes (and, for
training, loss, gradients and Adam) that import nothing of the program."""
