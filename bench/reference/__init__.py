"""Plain float32 reference of the configurations the benchmark runs."""
