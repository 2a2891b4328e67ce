"""Pot's chip benchmark: the cells of BENCHMARK.json on the served path."""
