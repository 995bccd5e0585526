"""Yardstick code of the benchmark: nothing here imports the program."""
