"""The benchmark's own code: traffic, weights, reference, operations and
bytes, trace reduction, peaks. Nothing here imports the program except
the two drivers (serve.py, train.py), which hold the system under test."""
