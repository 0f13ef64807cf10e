"""The benchmark's own code: file lookup, seeded generators, the adapter
to the system under test, the measured window, trace reduction and the
comparison that decides ``correct``."""
