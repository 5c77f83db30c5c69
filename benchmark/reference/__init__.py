"""Found by name from the data files; see benchmark/README.md."""
