"""Plain references: the configurations' mathematics in straightforward
``jax.numpy``, with no kernels, no schedule packing and no batching
tricks.  Nothing here imports the program or takes anything it made."""
