"""The benchmark's own code: traffic, weights, reference, FLOP counts,
trace reduction and the run itself. Nothing here is imported by the
program."""
