"""The chip benchmark's yardstick: traffic generation, the plain
reference and its pricing, the trace reduction and the comparison that
decides ``correct``.  Nothing here imports the program under test: the
modules under ``kinds/`` are the one place that drives it."""
