"""One module per kind of source. `read(run, args)` returns the metric's
value, or None where it finds nothing to read (the harness then leaves
the metric out of the line; a share of a peak is never reported as 0).
"""
