"""One reader per metric, found by the metric's name: `read(record)`
returns the number, or None where the run gives nothing to read."""
