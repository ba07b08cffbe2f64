"""The benchmark's own code: manifest, peaks, FLOP counts, traffic, trace
reduction, comparison. Imports nothing of the program under test."""
