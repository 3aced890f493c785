"""Per-layer metric readers: metrics/<metric>.py holds `read(run)`, which
returns the metric's value, or None where the run holds nothing to read."""
