"""The benchmark's yardstick: manifest lookup, traffic generation, the
trace reduction, the peak table and the operation and byte counts.
Nothing here is imported by the program under test."""
