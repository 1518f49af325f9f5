"""The benchmark's yardstick: workload specs, traffic, traces, work counts,
peaks and the comparison that decides ``correct``.  Nothing here imports
the program under test except ``systems/``, which drives it."""
