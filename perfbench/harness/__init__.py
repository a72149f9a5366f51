"""The general parts of the benchmark: nothing here names a cell."""
