"""The benchmark's own code: data and traffic generation, the plain
reference, the trace reduction and the work model. Nothing here imports
the program under test."""
