"""The yardstick: everything the benchmark measures with, kept apart
from the program so that a later PR cannot move it."""
