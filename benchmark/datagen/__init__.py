"""The benchmark's data generators (numpy only; no jax, no druid_tpu)."""
