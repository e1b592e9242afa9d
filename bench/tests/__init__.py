"""CPU tests of the benchmark (run by the repository's pytest)."""
