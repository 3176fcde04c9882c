"""The benchmark's yardstick: traffic, the load loop, trace reduction, the plain
reference and the comparison that decides ``correct``."""
