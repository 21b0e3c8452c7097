"""On-chip benchmark of the serving path (see README.md)."""
