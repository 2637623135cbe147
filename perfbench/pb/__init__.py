"""The benchmark's harness: traffic, driving, trace reduction, work counts."""
