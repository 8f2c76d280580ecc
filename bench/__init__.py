"""On-chip serving benchmark: a harness driven by data (see README.md)."""
