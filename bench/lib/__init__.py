"""The benchmark's yardstick: files, traffic, weights, counts, peaks, trace
reduction and the plain reference.  Nothing here imports the program."""
