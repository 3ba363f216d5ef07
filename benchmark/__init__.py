"""ompi_tpu's chip benchmark: the harness, its cells as data, and the
yardstick (traffic generation, references, trace reduction, peaks)."""
