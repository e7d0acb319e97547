"""The benchmark harness: specs, traffic, drivers, trace reduction, counts."""
