"""repro — optimized-broadcast reproduction grown into a jax serving/training
system."""
