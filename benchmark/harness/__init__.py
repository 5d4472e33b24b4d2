"""The benchmark's own yardstick: manifest, device gate, window loops, load
generator, trace reduction, operation and byte counts, and the comparison
that decides ``correct``.  Nothing here is imported by the program."""
