"""The yardstick: load clock, reference, probes, trace reduction, shapes."""
