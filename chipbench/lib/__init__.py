"""The yardstick: generators, plain references, work counts, peaks and the
trace reduction.  Nothing here imports the system under test."""
