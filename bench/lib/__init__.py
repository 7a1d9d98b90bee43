"""The benchmark's yardstick: device checks, traffic generation, window
arithmetic, trace reduction, peaks and operation counts.  Nothing here
imports the system under test; drivers (``bench/drivers``) do."""
