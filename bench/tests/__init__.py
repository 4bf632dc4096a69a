"""CPU tests of the benchmark: manifest, trace reduction, counts, the
reference and the comparison that decides ``correct``."""
