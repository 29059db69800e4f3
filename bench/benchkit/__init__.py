"""The benchmark's own code: traffic, loads, reference, trace reduction."""
