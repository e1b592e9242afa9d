"""Plain float32 reference of what the benchmark's cells run.

Written from the published descriptions (Llama-family decoder; the
paper's Algorithms 1-3 with the log grid Q_g and the uniform grid Q_x),
in straightforward ``jax.numpy``. It imports nothing of the program under
test and takes nothing the program made: the benchmark makes the
weights and the inputs from the seed, and both sides receive them.
"""
