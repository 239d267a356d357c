"""Plain references: each architecture's forward pass, the training
loss with its optimizer step, and the sampler's trajectory, in
straightforward `jax.numpy`, float32, `highest` matmul precision. They
import nothing of the program under test and take nothing it made:
weights, batches and conditioning come from the benchmark's own seeded
generators. `matmul` is the one place where a lower precision can be
put in, for the control that `correct` must fail."""
