"""Generators of the benchmark's data: catalog and query stream."""
