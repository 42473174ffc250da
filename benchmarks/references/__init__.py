"""Plain references, one module per configuration name: the
architecture's forward pass (for training, its loss too) in
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision,
with no kernels, cache or batching tricks, independent of the program
under test. They take the system's weights as a flat ``{name: array}``
dict, so both sides compute with the same numbers."""
