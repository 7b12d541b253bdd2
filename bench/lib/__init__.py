"""The benchmark's own code: cell lookup, traffic, the window driver, the
comparison with the reference, and the reduction of profiler traces."""
