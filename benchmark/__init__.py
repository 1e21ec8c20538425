"""The benchmark: the yardstick for ray_tpu on a TPU v5e (see README.md)."""
