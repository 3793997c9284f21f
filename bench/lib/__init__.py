"""The benchmark's yardstick: load generation, trace reduction, peaks and
the numerics that every architecture's plain reference shares (each
architecture's own reference, weights and FLOP and byte counts are in
``bench/arch/``).  Nothing here imports the program under test
(``repro``); later changes to the program cannot move it."""
