"""The benchmark: harness, data, references, trace reduction and tests
(see `bench/run.py`)."""
