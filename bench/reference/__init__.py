"""Plain references the benchmark checks the system against.  They import
nothing of the program and take nothing it made."""
