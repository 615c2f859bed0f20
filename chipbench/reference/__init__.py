"""Plain references the benchmark's check compares the program with.

They import nothing of the program and take nothing it made: the
operands and the weights are made again here from the seed."""
