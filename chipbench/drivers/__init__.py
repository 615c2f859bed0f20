"""Driver kinds, one file each; the harness loads them by name."""
