"""The on-chip benchmark's yardstick: everything here is the benchmark's own.

From the program under test it takes only the system itself (the TAG
runtime and its model's layer shapes) and the names of the kernels it runs.
Traffic generation, the updates made from the seed, the plain reference
fold, the byte counts, the peak table and the reduction from a profiler
trace to metrics all live in this package, so that a change to the program
cannot move them.
"""
