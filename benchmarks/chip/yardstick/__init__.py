"""The benchmark's own yardstick: peaks, work counts, trace reduction,
statistics and the generators of traffic.  Later changes to the program
cannot move it; it imports nothing of the program."""
