"""The on-chip benchmark: harness, yardstick and data.  See PERF.md."""
