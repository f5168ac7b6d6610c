"""Plain references the benchmark checks the timed path against."""
