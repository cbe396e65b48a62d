"""The chip benchmark: cells, traffic, work counts, trace reduction and the
plain reference.  ``python3 -m benchmarks.chip.run`` runs one cell."""
