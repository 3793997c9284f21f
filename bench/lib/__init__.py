"""The benchmark's yardstick: load generation, trace reduction, peaks, FLOP
and byte counts and the plain reference.  Nothing here imports the program
under test (``repro``); later changes to the program cannot move it."""
