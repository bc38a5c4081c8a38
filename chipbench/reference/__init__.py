"""Plain references: float32 ``jax.numpy`` at full matmul precision, no
import from the program, weights handed in by the benchmark."""
