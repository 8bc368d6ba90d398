"""The on-chip benchmark's yardstick: traffic, trace reduction, peaks,
operation and byte counts, and the comparison that decides ``correct``."""
