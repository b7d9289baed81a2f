"""Benchmark harness: paper tables T1-T10, stream and cluster benchmarks."""
