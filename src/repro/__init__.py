"""RePAST reproduction: second-order (K-FAC) training with
composed-precision block inversion, grown into a sharded jax system."""
