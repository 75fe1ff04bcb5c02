"""Shared pieces of the chip benchmark: cell specs, device checks, host
spans, trace reduction, FLOP counts and peaks (the benchmark's own copies,
independent of the program under test)."""
