"""The chip benchmark's harness: traffic, load generator, spans, trace
reduction, operation counts and the check against the plain reference
(see ``benchmarks/chip/run.py``)."""
