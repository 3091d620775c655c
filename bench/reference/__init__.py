"""Plain references the benchmark compares the served path with.  They
import nothing of the program."""
