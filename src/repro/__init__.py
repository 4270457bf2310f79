"""repro: FINN MVU reproduction on JAX/Pallas."""
