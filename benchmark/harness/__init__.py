"""The benchmark's harness: everything that is not one cell's data."""
