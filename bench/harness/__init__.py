"""The benchmark's yardstick: spec lookup, weights, traffic, load generator,
trace reduction, work counts and the correctness comparison."""
