"""Plain references: float32 jax.numpy at ``highest`` matmul precision, no
kernels, no cache, nothing imported from the program."""
