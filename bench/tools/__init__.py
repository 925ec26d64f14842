"""Scripts that set the benchmark's limits and sizes; run.py never calls
them."""
