"""The yardstick: everything the benchmark measures with, kept apart from the program."""
