"""The benchmark's own plain references. They import nothing of the
program under test: JAX and NumPy only."""
