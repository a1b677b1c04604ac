"""chipbench.harness: the yardstick — clock, percentiles, peaks, FLOP and
byte counts, seeded weights, trace reduction.  Nothing here imports the
program under test."""
