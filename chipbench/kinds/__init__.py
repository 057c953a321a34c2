"""Traffic kinds: one module per kind, found by name."""
