"""The benchmark's yardstick: everything here is the measurement, kept
apart from the program it measures (see ``perfbench/README.md``)."""
