"""HyPar-on-JAX reproduction package."""
