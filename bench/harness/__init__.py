"""The benchmark's own code that no model family changes: traffic, the
seed's keys, the reference's batching, the check, trace reduction and
the run itself. Each family's own code is in ``bench/families``.
Nothing here is imported by the program."""
